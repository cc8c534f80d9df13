"""Micro-batching serving tier: coalescing correctness, flush policy,
insert/search interleave, admission control, and the vectorized
cross-round merge regression against the old host-loop merge."""
import time

import numpy as np
import pytest

from repro.core import DHNSWEngine, EngineConfig
from repro.serve.batcher import (AdmissionError, ArrivalRateEWMA,
                                 BatchPolicy, MicroBatcher, TokenBucket)
from repro.serve.server import SearchServer

CFG = dict(mode="full", search_mode="scan", n_rep=16, b=3, ef=32,
           cache_frac=0.3, seed=3)


@pytest.fixture(scope="module")
def small_data(sift_small):
    return sift_small.data[:2000], sift_small.queries[:16]


@pytest.fixture(scope="module")
def engine(small_data):
    data, queries = small_data
    eng = DHNSWEngine(EngineConfig(**CFG)).build(data)
    eng.search(queries[:8], k=10)        # warm the jit caches
    return eng


def test_coalesce_bit_identical_to_serial(engine, small_data):
    """N concurrent requests -> ONE fused engine call, results
    bit-identical to per-request serial search on a fresh engine."""
    data, queries = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_batch=64, max_wait_s=0.1),
                      autostart=False)
    futs = [mb.submit_search(queries[i], k=10) for i in range(8)]
    mb.start()
    results = [f.result(timeout=60) for f in futs]
    mb.stop()
    snap = mb.metrics.snapshot()
    assert snap["n_fused_calls"] == 1
    assert snap["mean_fused_batch"] == 8.0
    assert snap["n_requests"] == 8

    serial = DHNSWEngine(EngineConfig(**CFG)).build(data)
    for i, (d, g, st) in enumerate(results):
        ds, gs, _ = serial.search(queries[i:i + 1], k=10)
        assert np.array_equal(g, gs), i
        assert np.allclose(d, ds), i
        assert st["fused_batch"] == 8
        assert st["queue_s"] >= 0 and st["total_s"] >= st["serve_s"]


def test_mixed_k_requests_prefix_consistent(engine, small_data):
    """One window with different k's: fused at max k, sliced per request."""
    _, queries = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_wait_s=0.1), autostart=False)
    f5 = mb.submit_search(queries[0], k=5)
    f10 = mb.submit_search(queries[0], k=10)
    mb.start()
    d5, g5, _ = f5.result(timeout=60)
    d10, g10, _ = f10.result(timeout=60)
    mb.stop()
    assert g5.shape == (1, 5) and g10.shape == (1, 10)
    assert np.array_equal(g5[0], g10[0, :5])


def test_max_wait_flushes_partial_window(engine, small_data):
    """A lone request must not wait for max_batch to fill."""
    _, queries = small_data
    with MicroBatcher(engine, BatchPolicy(max_batch=4096,
                                          max_wait_s=0.02)) as mb:
        t0 = time.perf_counter()
        d, g, st = mb.search(queries[0], k=10)
        elapsed = time.perf_counter() - t0
    assert st["fused_batch"] == 1
    assert elapsed < 10          # generous: CI boxes stall; policy is 20ms


def test_insert_search_interleave_preserves_order(engine, small_data):
    """search | insert X | search X queued in one window: the trailing
    search must see X (consecutive-run grouping keeps arrival order)."""
    data, _ = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_wait_s=0.05), autostart=False)
    new = data[7] + np.float32(0.0007)
    f_pre = mb.submit_search(data[0], k=5)
    f_ins = mb.submit_insert(new)
    f_post = mb.submit_search(new, k=3)
    mb.start()
    gids = f_ins.result(timeout=60)
    _, g_post, _ = f_post.result(timeout=60)
    f_pre.result(timeout=60)
    mb.stop()
    assert len(gids) == 1
    assert gids[0] in g_post[0]
    assert mb.metrics.snapshot()["n_fused_calls"] == 3  # s | i | s runs


def test_token_bucket_admission():
    tb = TokenBucket(rate=1.0, burst=2)
    assert tb.acquire(2, block=False)
    assert not tb.acquire(1, block=False)   # bucket drained
    time.sleep(1.1)
    assert tb.acquire(1, block=False)       # refilled ~1 token

    eng_stub = None  # admission fires before the engine is touched
    mb = MicroBatcher(eng_stub, BatchPolicy(rate=1.0, burst=1,
                                            admission_block=False),
                      autostart=False)
    mb.submit_search(np.zeros(8, np.float32), k=1)
    with pytest.raises(AdmissionError):
        mb.submit_search(np.zeros(8, np.float32), k=1)
    assert mb.metrics.n_rejected == 1


def test_per_tenant_admission_isolates_tenants():
    """One tenant over its rate gets rejected WITHOUT draining another
    tenant's budget (the global bucket is disabled here), and the stats
    snapshot carries per-tenant admit/reject counts."""
    mb = MicroBatcher(None, BatchPolicy(tenant_rate=1.0, tenant_burst=2,
                                        admission_block=False),
                      autostart=False)
    q = np.zeros(8, np.float32)
    mb.submit_search(q, k=1, tenant="a")
    mb.submit_search(q, k=1, tenant="a")        # drains a's bucket
    with pytest.raises(AdmissionError):
        mb.submit_search(q, k=1, tenant="a")
    # tenant b is untouched by a's exhaustion
    mb.submit_search(q, k=1, tenant="b")
    snap = mb.metrics.snapshot()
    assert snap["tenants"]["a"] == {"admitted": 2, "rejected": 1,
                                    "queued": 2, "served": 0, "share": 0.0}
    assert snap["tenants"]["b"] == {"admitted": 1, "rejected": 0,
                                    "queued": 1, "served": 0, "share": 0.0}
    assert snap["n_rejected"] == 1


def test_tenant_rejection_does_not_drain_global_bucket():
    """A tenant-rejected request must not consume shared global tokens:
    one tenant flooding past ITS rate leaves the global budget (and so
    every other tenant's admission) untouched."""
    mb = MicroBatcher(None, BatchPolicy(rate=1.0, burst=4,
                                        tenant_rate=1.0, tenant_burst=2,
                                        admission_block=False),
                      autostart=False)
    q = np.zeros(8, np.float32)
    mb.submit_search(q, k=1, tenant="flood")
    mb.submit_search(q, k=1, tenant="flood")     # drains flood's bucket
    for _ in range(10):                          # all tenant-rejected
        with pytest.raises(AdmissionError):
            mb.submit_search(q, k=1, tenant="flood")
    # global budget: burst 4, only 2 consumed -> "quiet" still admits
    mb.submit_search(q, k=1, tenant="quiet")
    mb.submit_search(q, k=1, tenant="quiet")
    snap = mb.metrics.snapshot()
    assert snap["tenants"]["quiet"] == {"admitted": 2, "rejected": 0,
                                        "queued": 2, "served": 0,
                                        "share": 0.0}
    assert snap["tenants"]["flood"]["rejected"] == 10


def test_per_tenant_queue_depth_and_dispatch(engine, small_data):
    """Queue depth per tenant: counted while pending, drained to zero
    once dispatched; results are per-request correct."""
    _, queries = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_batch=64, max_wait_s=0.05),
                      autostart=False)
    futs = [mb.submit_search(queries[i], k=10, tenant=t)
            for i, t in enumerate(("a", "a", "b"))]
    depth = mb.metrics.snapshot()["tenants"]
    assert depth["a"]["queued"] == 2 and depth["b"]["queued"] == 1
    assert depth["a"]["admitted"] == 2
    mb.start()
    for f in futs:
        d, g, _ = f.result(timeout=60)
        assert g.shape == (1, 10)
    mb.stop()
    after = mb.metrics.snapshot()["tenants"]
    assert after["a"]["queued"] == 0 and after["b"]["queued"] == 0


def test_default_tenant_untouched_by_policy(engine, small_data):
    """No tenant key + tenant_rate=0: admission behaves exactly as
    before and everything lands under the "-" tenant."""
    _, queries = small_data
    with SearchServer(engine, BatchPolicy(max_wait_s=0.005)) as srv:
        srv.search(queries[0], k=10)
        snap = srv.stats()
    assert snap["tenants"]["-"]["admitted"] == 1
    assert snap["tenants"]["-"]["queued"] == 0


def test_adaptive_wait_shrinks_under_load_grows_idle():
    """The ROADMAP item: the window budget scales with the observed
    arrival rate — tight under load, growing toward the cap when idle
    (synthetic clocks, no threads)."""
    pol = BatchPolicy(max_batch=64, max_wait_s=5e-3, adaptive_wait=True,
                      min_wait_s=1e-4)

    hot = ArrivalRateEWMA(alpha=0.2)
    for i in range(200):                 # 20 us apart: heavy load
        hot.observe(i * 2e-5)
    idle = ArrivalRateEWMA(alpha=0.2)
    for i in range(20):                  # 50 ms apart: sparse
        idle.observe(i * 5e-2)

    w_hot = hot.wait_budget_s(pol)
    w_idle = idle.wait_budget_s(pol)
    assert w_hot < w_idle                # shrinks under load
    assert w_idle == pol.max_wait_s      # grows back to the cap when idle
    assert pol.min_wait_s <= w_hot < pol.max_wait_s
    # extreme load pins the floor
    slam = ArrivalRateEWMA(alpha=0.2)
    for i in range(500):
        slam.observe(i * 1e-8)
    assert slam.wait_budget_s(pol) == pol.min_wait_s
    # non-adaptive policies are untouched
    fixed = BatchPolicy(max_batch=64, max_wait_s=5e-3)
    assert hot.wait_budget_s(fixed) == fixed.max_wait_s
    # no signal yet -> conservative cap
    assert ArrivalRateEWMA().wait_budget_s(pol) == pol.max_wait_s


def test_adaptive_wait_collapses_on_empty_queue():
    """A window whose opener found the queue EMPTY at enqueue time
    collapses straight to the floor — holding it open cannot coalesce
    what isn't there — while a busy-queue opener keeps the rate-derived
    budget, and non-adaptive policies ignore the hint entirely."""
    pol = BatchPolicy(max_batch=64, max_wait_s=5e-3, adaptive_wait=True,
                      min_wait_s=1e-4)
    idle = ArrivalRateEWMA(alpha=0.2)
    for i in range(20):
        idle.observe(i * 5e-2)           # sparse arrivals: budget at cap
    assert idle.wait_budget_s(pol) == pol.max_wait_s
    assert idle.wait_budget_s(pol, queue_empty=True) == pol.min_wait_s
    assert idle.wait_budget_s(pol, queue_empty=False) == pol.max_wait_s
    # non-adaptive: the hint must not shrink the fixed window
    fixed = BatchPolicy(max_batch=64, max_wait_s=5e-3)
    assert idle.wait_budget_s(fixed, queue_empty=True) == fixed.max_wait_s


def test_empty_at_enqueue_flag_set_by_batcher(engine, small_data):
    """The batcher records the queue state the opener saw: a request
    submitted into an empty queue is flagged; one submitted behind a
    backlog is not — and the adaptive loop still answers correctly."""
    _, queries = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_batch=64, max_wait_s=0.05,
                                          adaptive_wait=True,
                                          min_wait_s=1e-4),
                      autostart=False)
    f0 = mb.submit_search(queries[0], k=10)
    f1 = mb.submit_search(queries[1], k=10)
    with mb._cv:
        flags = [r.empty_at_enqueue for r in mb._queue]
    assert flags == [True, False]
    mb.start()
    for f in (f0, f1):
        r = f.result(timeout=60)
        assert r[1].shape == (1, 10)
    mb.stop()


def test_adaptive_wait_live_batcher(engine, small_data):
    """End-to-end: an adaptive batcher still coalesces and answers
    correctly, and its observed EWMA reflects the submissions."""
    _, queries = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_batch=64, max_wait_s=0.05,
                                          adaptive_wait=True),
                      autostart=False)
    futs = [mb.submit_search(queries[i], k=10) for i in range(6)]
    mb.start()
    res = [f.result(timeout=60) for f in futs]
    mb.stop()
    assert len(res) == 6 and all(r[1].shape == (1, 10) for r in res)
    assert mb.arrivals.interarrival_s() is not None


def test_server_stats_snapshot(engine, small_data):
    _, queries = small_data
    with SearchServer(engine, BatchPolicy(max_wait_s=0.005)) as srv:
        for i in range(4):
            srv.search(queries[i], k=10)
        snap = srv.stats()
    assert snap["n_requests"] == 4
    assert snap["p50_ms"] > 0 and snap["p99_ms"] >= snap["p50_ms"]
    for key in ("queue_s", "route_s", "plan_s", "fetch_model_s", "serve_s"):
        assert snap["breakdown_s"][key] >= 0


def test_wfq_drains_by_weight_not_arrival():
    """Deficit round-robin: with weights 3:1 and tenant B's whole
    backlog queued FIRST, a window still drains ~3 A rows per B row —
    and arrival order is preserved within each tenant."""
    from repro.serve.batcher import _Request

    pol = BatchPolicy(max_batch=16, wfq=True, wfq_quantum=1,
                      tenant_weight={"A": 3.0, "B": 1.0})
    mb = MicroBatcher(None, pol, autostart=False)
    for i in range(40):
        mb._enqueue(_Request("search", np.zeros((1, 4), np.float32), i,
                             time.perf_counter(), "B"))
    for i in range(40):
        mb._enqueue(_Request("search", np.zeros((1, 4), np.float32), i,
                             time.perf_counter(), "A"))
    for _ in range(2):
        win = mb._take_window()
        kinds = [r.tenant for r in win]
        assert kinds.count("A") == 12 and kinds.count("B") == 4
        for t in ("A", "B"):   # per-tenant FIFO (k carries arrival index)
            ks = [r.k for r in win if r.tenant == t]
            assert ks == sorted(ks)
    # FIFO default untouched: no weights, no wfq flag
    assert not BatchPolicy().fair_queue


def test_wfq_deficit_resets_when_backlog_drains():
    """A tenant that goes idle must not bank credit: classic DRR drops
    the deficit once its queue empties (the tenant is pruned from the
    service list entirely, so long-lived servers with many tenant keys
    don't grow the sweep without bound)."""
    from repro.serve.batcher import _Request

    pol = BatchPolicy(max_batch=8, wfq=True, wfq_quantum=1,
                      tenant_weight={"A": 5.0})
    mb = MicroBatcher(None, pol, autostart=False)
    mb._enqueue(_Request("search", np.zeros((1, 4), np.float32), 0,
                         time.perf_counter(), "A"))
    win = mb._take_window()
    assert [r.tenant for r in win] == ["A"]
    assert mb._deficit.get("A", 0.0) == 0.0
    assert "A" not in mb._rr


def test_wfq_rotating_start_prevents_tail_starvation():
    """Regression: a window that fills before the sweep reaches the
    tail tenants must not restart at the same head tenant — the start
    rotates, so every backlogged tenant is served within a bounded
    number of windows."""
    from repro.serve.batcher import _Request

    tenants = [f"t{i}" for i in range(9)]
    pol = BatchPolicy(max_batch=8, wfq=True, wfq_quantum=8)
    mb = MicroBatcher(None, pol, autostart=False)
    for _ in range(4):                       # deep equal backlogs
        for t in tenants:
            mb._enqueue(_Request("search", np.zeros((1, 4), np.float32),
                                 0, time.perf_counter(), t))
    served = []
    for _ in range(9):                       # 9 windows x 8 rows
        served.extend(r.tenant for r in mb._take_window())
    from collections import Counter
    counts = Counter(served)
    assert set(counts) == set(tenants), "no tenant may be starved"
    assert max(counts.values()) - min(counts.values()) <= 8


def test_wfq_zero_weight_tenant_cannot_stall_the_drain():
    """Regression: a zero/near-zero weight must not busy-spin the drain
    loop (which runs while HOLDING the batcher lock) — when no tenant
    can afford its queue head in a full sweep, the head is forced
    through instead of spinning."""
    from repro.serve.batcher import _Request

    pol = BatchPolicy(max_batch=64, wfq=True, wfq_quantum=8,
                      tenant_weight={"bad": 0.0})
    mb = MicroBatcher(None, pol, autostart=False)
    for _ in range(3):
        mb._enqueue(_Request("search", np.zeros((32, 4), np.float32), 0,
                             time.perf_counter(), "bad"))
    t0 = time.perf_counter()
    win = mb._take_window()
    assert time.perf_counter() - t0 < 1.0, "drain must not spin"
    assert sum(r.vecs.shape[0] for r in win) >= 32


def test_wfq_serves_correct_results_and_share(engine, small_data):
    """End-to-end through the dispatcher: fair-queued requests still get
    their own correct answers, and stats()["tenants"] reports the
    served-rows share."""
    _, queries = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_batch=64, max_wait_s=0.05,
                                          wfq=True,
                                          tenant_weight={"a": 2.0}),
                      autostart=False)
    futs = [(i, mb.submit_search(queries[i], k=10,
                                 tenant="a" if i % 4 else "b"))
            for i in range(8)]
    mb.start()
    serial = {i: f.result(timeout=60) for i, f in futs}
    mb.stop()
    for i, (d, g, _) in serial.items():
        assert g.shape == (1, 10)
    snap = mb.metrics.snapshot()
    t = snap["tenants"]
    assert t["a"]["served"] == 6 and t["b"]["served"] == 2
    assert t["a"]["share"] == pytest.approx(0.75)
    assert t["b"]["share"] == pytest.approx(0.25)


def test_wfq_preserves_per_tenant_insert_search_order(engine, small_data):
    """Within one tenant, a search queued after an insert still observes
    the inserted vector under WFQ (cross-tenant reorder is allowed,
    within-tenant order is not)."""
    data, queries = small_data
    mb = MicroBatcher(engine, BatchPolicy(max_wait_s=0.05, wfq=True),
                      autostart=False)
    new = data[11] + np.float32(0.0011)
    noise = [mb.submit_search(queries[i % 8], k=5, tenant="other")
             for i in range(4)]
    f_ins = mb.submit_insert(new, tenant="x")
    f_post = mb.submit_search(new, k=3, tenant="x")
    mb.start()
    gids = f_ins.result(timeout=60)
    _, g_post, _ = f_post.result(timeout=60)
    for f in noise:
        f.result(timeout=60)
    mb.stop()
    assert gids[0] in g_post[0]


def test_vectorized_merge_matches_host_loop_merge():
    """Regression: DS.merge_ranked == the old per-pair host fold (stable
    argsort over [running | pair]) on a fixed seed, ties included."""
    import jax.numpy as jnp

    from repro.core import device_store as DS
    from repro.core.scheduler import _pair_ranks

    rng = np.random.default_rng(42)
    B, k, n = 13, 10, 37
    run_d = np.sort(rng.standard_normal((B, k)).astype(np.float32) ** 2,
                    axis=1)
    run_g = rng.integers(0, 10_000, (B, k)).astype(np.int32)
    qi = rng.integers(0, B, n)
    d = np.sort(rng.standard_normal((n, k)).astype(np.float32) ** 2, axis=1)
    d[5] = run_d[int(qi[5])]                # exact ties across run/new
    g = rng.integers(10_000, 20_000, (n, k)).astype(np.int32)

    # the old engine step-3 host loop, verbatim
    want_d, want_g = run_d.copy(), run_g.astype(np.int64)
    for j in range(n):
        q = int(qi[j])
        md = np.concatenate([want_d[q], d[j]])
        mg = np.concatenate([want_g[q], g[j]])
        order = np.argsort(md, kind="stable")[:k]
        want_d[q], want_g[q] = md[order], mg[order]

    pairs = np.stack([qi, np.zeros(n, np.int64)], axis=1)
    ranks = _pair_ranks(pairs)
    got_d, got_g = DS.merge_ranked(
        jnp.asarray(run_d), jnp.asarray(run_g),
        jnp.asarray(qi, jnp.int32), jnp.asarray(ranks, jnp.int32),
        jnp.asarray(d), jnp.asarray(g), n_lanes=int(ranks.max()) + 1)
    assert np.array_equal(np.asarray(got_d), want_d)
    assert np.array_equal(np.asarray(got_g).astype(np.int64), want_g)
