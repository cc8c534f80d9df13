"""Quantized resident tier: codec bounds, staged-search acceptance
(recall + bytes), scheme composition, insert coherence, serve routing,
and the quant="none" regression guard."""
import numpy as np
import pytest

from repro.core import DHNSWEngine, EngineConfig, recall_at_k
from repro.core.cost_model import RDMA_100G
from repro.quant.codec import dequantize_groups, quantize_groups

CFG = dict(mode="full", search_mode="scan", n_rep=32, b=6, ef=48,
           cache_frac=0.25, doorbell=16, fabric=RDMA_100G, seed=3)


@pytest.fixture(scope="module")
def qds():
    from repro.data.synthetic import sift_like
    return sift_like(n=3000, n_queries=256, seed=7)


@pytest.fixture(scope="module")
def eng_none(qds):
    return DHNSWEngine(EngineConfig(**CFG)).build(qds.data)


@pytest.fixture(scope="module")
def eng_int8(qds):
    return DHNSWEngine(EngineConfig(quant="int8", **CFG)).build(qds.data)


# ------------------------------------------------------------------ codec

def test_codec_roundtrip_error_bound(rng):
    x = rng.standard_normal((100, 128)).astype(np.float32)
    codes, scales = quantize_groups(x, 32)
    xr = dequantize_groups(codes, scales, 32)
    # symmetric int8: error <= scale/2 = absmax/254 per group
    gmax = np.abs(x.reshape(100, 4, 32)).max(-1, keepdims=True)
    bound = np.broadcast_to(gmax / 254 + 1e-7, (100, 4, 32)).reshape(100, 128)
    assert (np.abs(xr - x) <= bound).all()
    assert codes.dtype == np.int8


def test_codec_zero_groups_safe():
    x = np.zeros((4, 64), np.float32)
    codes, scales = quantize_groups(x, 16)
    assert (codes == 0).all()
    assert np.isfinite(scales).all()
    assert (dequantize_groups(codes, scales, 16) == 0).all()


def test_codec_group_must_divide_dim():
    with pytest.raises(AssertionError):
        quantize_groups(np.zeros((2, 100), np.float32), 32)


# ------------------------------------------------- acceptance criteria

def test_int8_recall_and_bytes_vs_none(qds, eng_none, eng_int8):
    """The ISSUE's bar: recall@10 >= 0.85 AND >= 4x fewer fetched bytes
    than quant=none at the same cache byte budget, over a multi-batch
    workload (tier reuse included, cold start included)."""
    batches = [qds.queries[i * 64:(i + 1) * 64] for i in range(4)]
    totals = {}
    recalls = {}
    for name, eng in (("none", eng_none), ("int8", eng_int8)):
        tot, recs = 0.0, []
        for i, qb in enumerate(batches):
            _, g, st = eng.search(qb, k=10)
            tot += st["net"]["bytes"]
            recs.append(recall_at_k(g, qds.gt_ids[i * 64:(i + 1) * 64, :10]))
        totals[name], recalls[name] = tot, float(np.mean(recs))
    assert recalls["int8"] >= 0.85, recalls
    assert totals["none"] >= 4.0 * totals["int8"], totals
    # staged search must not cost recall vs the exact scan at the same b
    assert recalls["int8"] >= recalls["none"] - 0.02, recalls


def test_bytes_saved_counted(qds, eng_int8):
    _, _, st = eng_int8.search(qds.queries[:32], k=10)
    assert st["net"]["bytes_saved"] > 0
    assert st["quant"] == "int8"
    assert st["rerank_m"] >= 10


# --------------------------------------------------- scheme composition

def test_schemes_compose_with_quant(qds):
    """naive / no_doorbell / full with int8 differ ONLY in transfer
    strategy: identical ids, paper-shaped round-trip ordering."""
    common = dict(search_mode="scan", n_rep=12, b=3, ef=48,
                  cache_frac=0.25, seed=3, fabric=RDMA_100G, quant="int8")
    res = {}
    for mode in ("naive", "no_doorbell", "full"):
        eng = DHNSWEngine(EngineConfig(mode=mode, **common)).build(
            qds.data[:1500])
        _, g, st = eng.search(qds.queries[:32], k=10)
        res[mode] = (g, st)
    assert np.array_equal(res["naive"][0], res["no_doorbell"][0])
    assert np.array_equal(res["naive"][0], res["full"][0])
    rt = {m: res[m][1]["net"]["round_trips"] for m in res}
    assert rt["naive"] > rt["no_doorbell"] >= rt["full"]


def test_graph_mode_composes_with_quant(qds):
    eng = DHNSWEngine(EngineConfig(mode="full", search_mode="graph",
                                   n_rep=12, b=4, ef=48, cache_frac=0.3,
                                   seed=3, quant="int8")).build(
        qds.data[:1500])
    _, g, st = eng.search(qds.queries[:32], k=10)
    gt_d, gt_i = _brute(qds.data[:1500], qds.queries[:32], 10)
    assert recall_at_k(g, gt_i) >= 0.6   # graph walk at small b
    assert st["net"]["bytes_saved"] > 0


def _brute(data, queries, k):
    from repro.core.hnsw import brute_force_knn
    return brute_force_knn(data, queries, k)


# ------------------------------------------------------ none regression

def test_quant_none_unaffected(qds, eng_none, eng_int8):
    """Regression guard: the default path must be bit-identical whether
    or not quantized engines exist beside it, and must never emit quant
    stats keys."""
    d0, g0, st0 = eng_none.search(qds.queries[:16], k=10)
    eng_int8.search(qds.queries[:16], k=10)   # interleave a staged search
    d1, g1, st1 = eng_none.search(qds.queries[:16], k=10)
    assert np.array_equal(g0, g1)
    assert np.array_equal(d0, d1)
    for st in (st0, st1):
        assert "quant" not in st and "rerank_m" not in st
        assert st["net"]["bytes_saved"] == 0.0
    assert eng_none.tiers is None
    assert eng_none.store.qvec_buf is None


def test_exact_tier_admission_after_reuse(qds):
    """Hot re-rank partitions get promoted to the exact tier once their
    cumulative missed rows outweigh one span fetch — and their rows stop
    being charged."""
    eng = DHNSWEngine(EngineConfig(quant="int8", **CFG)).build(qds.data)
    qb = qds.queries[:64]
    threshold = eng.store.spec.partition_bytes() // eng.store.spec.row_bytes()
    admitted = hit_rows = 0
    # same batch over and over -> the hottest re-rank partition crosses
    # the cost threshold (~`threshold` missed rows) and gets promoted
    for _ in range(12):
        _, _, st = eng.search(qb, k=10)
        admitted += st["exact_admitted"]
        hit_rows += st["rerank_hit_rows"]
        if admitted and hit_rows:
            break
    assert admitted >= 1
    assert hit_rows > 0
    assert len(eng.tiers.exact.resident()) >= 1


# ----------------------------------------------------------- insert

def test_insert_searchable_with_quant(qds):
    eng = DHNSWEngine(EngineConfig(mode="full", search_mode="scan",
                                   n_rep=16, b=2, ef=32, cache_frac=0.4,
                                   seed=3, quant="int8")).build(
        qds.data[:2000])
    new = qds.data[2000:2010] + 0.001
    gids = eng.insert(new)
    d, g, _ = eng.search(new, k=3)
    found = np.mean([gid in g[i] for i, gid in enumerate(gids)])
    assert found >= 0.9, (found, g[:3], gids[:3])


def test_insert_overflow_repack_with_quant(qds):
    eng = DHNSWEngine(EngineConfig(mode="full", search_mode="scan",
                                   n_rep=8, b=2, ef=32, cache_frac=0.5,
                                   seed=3, quant="int8")).build(
        qds.data[:1000])
    ov = eng.store.spec.ov_cap
    base = qds.data[42]
    new = base[None, :] + 0.0005 * np.random.default_rng(0).standard_normal(
        (ov + 3, eng.store.spec.dim)).astype(np.float32)
    gids = eng.insert(new)
    d, g, _ = eng.search(new[:8], k=3)
    found = np.mean([gid in g[i] for i, gid in enumerate(gids[:8])])
    assert found >= 0.8, found
    # the quantized mirror tracked the repack: codes decode near vec_buf
    store = eng.store
    xr = dequantize_groups(store.qvec_buf, store.qscale_buf,
                           store.spec.quant_group)
    assert np.abs(xr - store.vec_buf).max() <= (
        np.abs(store.vec_buf).max() / 200)


# ----------------------------------------------- flat kernel route

def test_flat_kernel_route_dense_resident(qds):
    """With a dense-resident quantized tier (capacity >= n_partitions)
    and scan-mode stage 1, quant_kernel="auto" routes through ONE flat
    ``quant_topk`` scan: recall must not regress vs the per-pair jnp
    path, warm stage-1 must be wire-free, and the Pallas kernel and the
    jnp oracle route must agree exactly."""
    common = dict(mode="full", search_mode="scan", n_rep=16, b=3, ef=32,
                  cache_frac=0.6, seed=3, quant="int8")
    jnp_eng = DHNSWEngine(EngineConfig(**common)).build(qds.data)
    flat = DHNSWEngine(EngineConfig(quant_kernel="auto", **common)).build(
        qds.data)
    assert flat.client._flat_kernel_active()
    q = qds.queries[:64]
    _, gj, _ = jnp_eng.search(q, k=10)
    df, gf, stf = flat.search(q, k=10)
    assert stf["quant_kernel"] == "flat"
    assert stf["flat_rows"] == len(qds.data)
    rj = recall_at_k(gj, qds.gt_ids[:64, :10])
    rf = recall_at_k(gf, qds.gt_ids[:64, :10])
    assert rf >= rj - 1e-9, (rf, rj)   # flat scans every resident row
    # warm: the whole int8 DB is resident -> stage 1 moves zero bytes;
    # only stage-2 row fetches remain on the wire
    _, _, warm = flat.search(q, k=10)
    row_b = flat.store.spec.row_bytes()
    assert warm["net"]["bytes"] <= warm["rerank_rows"] * row_b + 1e-9
    # fallback guard: a sparse tier must keep the per-pair path
    sparse = DHNSWEngine(EngineConfig(mode="full", search_mode="scan",
                                      n_rep=16, b=3, ef=32, cache_frac=0.1,
                                      seed=3, quant="int8",
                                      quant_kernel="auto")).build(qds.data)
    assert not sparse.client._flat_kernel_active()
    _, _, sts = sparse.search(q[:8], k=10)
    assert "quant_kernel" not in sts


def test_auto_kernel_picks_ref_impl_on_cpu(qds):
    """quant_kernel="auto" must select the jnp reference stage-1 on the
    CPU backend (where Pallas would run interpreted, ~8x slower) and the
    Pallas kernel on real accelerators; the choice is reported in
    ``stats["stage1_impl"]``, and an explicit "ref" request always gets
    the ref path."""
    import jax

    from repro.kernels.quant_topk.ops import auto_use_ref
    on_cpu = jax.default_backend() == "cpu"
    assert auto_use_ref() == on_cpu
    common = dict(mode="full", search_mode="scan", n_rep=16, b=3, ef=32,
                  cache_frac=0.6, seed=3, quant="int8")
    auto = DHNSWEngine(EngineConfig(quant_kernel="auto", **common)).build(
        qds.data)
    assert auto.client._flat_kernel_active()
    _, _, st = auto.search(qds.queries[:8], k=10)
    assert st["stage1_impl"] == ("ref" if on_cpu else "pallas")
    ref = DHNSWEngine(EngineConfig(quant_kernel="ref", **common)).build(
        qds.data)
    _, _, st_ref = ref.search(qds.queries[:8], k=10)
    assert st_ref["stage1_impl"] == "ref"


def test_flat_kernel_insert_stays_coherent(qds):
    """Appends keep the dense-resident flat view coherent without a
    resync: the inserted vector is immediately a stage-1 candidate."""
    eng = DHNSWEngine(EngineConfig(mode="full", search_mode="scan",
                                   n_rep=16, b=3, ef=32, cache_frac=0.6,
                                   seed=3, quant="int8",
                                   quant_kernel="auto")).build(
        qds.data[:2000])
    eng.search(qds.queries[:8], k=10)         # cold sync
    new = qds.queries[:4] + 0.001
    gids = eng.insert(new)
    d, g, st = eng.search(new, k=3)
    assert st.get("quant_kernel") == "flat"
    found = np.mean([gid in g[i] for i, gid in enumerate(gids)])
    assert found == 1.0, (found, g, gids)


def test_server_counts_stage1_merge_rounds(qds, monkeypatch):
    """A served call through the Pallas stage 1 folds the kernel's merge
    rounds and tiles into ``stats()["engine"]``; the jnp ref path counts
    neither."""
    from repro.kernels.quant_topk import ops
    from repro.serve.batcher import BatchPolicy
    from repro.serve.server import SearchServer

    monkeypatch.setattr(ops, "auto_use_ref", lambda: False)
    eng = DHNSWEngine(EngineConfig(mode="full", search_mode="scan",
                                   n_rep=16, b=3, ef=32, cache_frac=0.6,
                                   seed=3, quant="int8",
                                   quant_kernel="auto")).build(
        qds.data[:2000])
    with SearchServer(eng, BatchPolicy(max_batch=8,
                                       max_wait_s=0.05)) as srv:
        futs = [srv.search_async(qds.queries[i], k=10) for i in range(8)]
        stats = [f.result(timeout=300)[2] for f in futs]
        snap = srv.stats()
    assert stats[0]["stage1_impl"] == "pallas"
    engine = snap["engine"]
    tiles = 2048 // 512       # one q-tile; 2,000 rows padded to 2,048
    assert engine["stage1_tiles"] == tiles * snap["n_fused_calls"] > 0
    assert 0 < engine["stage1_merge_rounds"] <= 20 * engine["stage1_tiles"]
    monkeypatch.setattr(ops, "auto_use_ref", lambda: True)
    _, _, st = eng.search(qds.queries[:8], k=10)
    assert st["stage1_impl"] == "ref"
    assert st["stage1_merge_rounds"] == st["stage1_tiles"] == 0


# ------------------------------------------------------------ serving

def test_serve_routes_through_staged_path(qds, eng_int8):
    """Fused batches from the micro-batcher hit the SAME staged path:
    results match per-request searches on a fresh engine, and the server
    surfaces the NetLedger bytes breakdown."""
    from repro.serve.batcher import BatchPolicy
    from repro.serve.server import SearchServer

    queries = qds.queries[:8]
    with SearchServer(eng_int8, BatchPolicy(max_batch=64,
                                            max_wait_s=0.05)) as srv:
        futs = [srv.search_async(queries[i], k=10) for i in range(8)]
        results = [f.result(timeout=120) for f in futs]
        snap = srv.stats()
    fresh = DHNSWEngine(EngineConfig(quant="int8", **CFG)).build(qds.data)
    for i, (d, g, st) in enumerate(results):
        df, gf, _ = fresh.search(queries[i:i + 1], k=10)
        assert np.array_equal(g, gf), i
        assert np.allclose(d, df), i
        assert st["quant"] == "int8"
    assert snap["net"]["bytes_fetched"] >= 0
    assert snap["net"]["bytes_saved"] > 0
