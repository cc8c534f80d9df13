"""repro.obs — end-to-end tracing and metrics.

Four contracts:

* **tracer mechanics** — nesting via the per-thread parent stack, ring
  capacity + drop accounting, and the disabled tracer being a true
  no-op (shared null span, nothing allocated or recorded).
* **span tree shape** — a search through ``SearchServer`` produces the
  documented taxonomy: pool verb events nest under ``compute.fetch``
  which nests under ``compute.round`` / ``compute.search`` under the
  serve window spans.
* **wire propagation** — against a loopback ``PoolServer`` the client
  negotiates FLAG_TRACE at PING, stamps verb frames with trace context,
  and harvests server-side service-time spans whose durations are
  covered by the matching client-side ``net.*`` span; a server that
  never acks the flag (old server) is simply never sent trace bytes.
* **observability is free** — with tracing off OR on, results and the
  NetLedger are bit-identical across every transport x quant combo;
  only the tracer's own buffer grows.

Plus exporter round-trips (Chrome trace JSON, Prometheus text, the
report CLI) and the serving benchmark's counted-pass determinism that
``benchmarks/perf_gate.py`` relies on.
"""
from __future__ import annotations

import json
import re
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import DHNSWEngine, EngineConfig
from repro.core.cost_model import RDMA_100G
from repro.net.server import PoolServer
from repro.obs import report
from repro.obs.hist import (HIST_BOUNDS, LatencyHistogram, StragglerDetector,
                            VerbShardHist)
from repro.obs.metrics import render_pool_server, render_prometheus
from repro.obs.slo import SLO, SLOTracker, parse_slo
from repro.obs.trace import TRACER, Tracer, chrome_trace, load_trace
from repro.pool.protocol import PoolUnavailableError
from repro.rdma.inject import InjectedFault, WRInjector
from repro.serve.batcher import BatchPolicy
from repro.serve.server import SearchServer

CFG = dict(mode="full", search_mode="scan", n_rep=12, b=3, ef=32,
           cache_frac=0.25, seed=3)


@pytest.fixture(autouse=True)
def _tracer_guard():
    """Every test leaves the process-global tracer disabled."""
    yield
    TRACER.disable()


@pytest.fixture()
def pds(sift_small):
    return sift_small.data[:1200], sift_small.queries[:16]


def _by_id(spans):
    return {s["id"]: s for s in spans}


def _ancestors(span, idx):
    out = []
    while span["parent"]:
        span = idx[span["parent"]]
        out.append(span["name"])
    return out


# ------------------------------------------------------------ mechanics


def test_disabled_tracer_is_noop():
    tr = Tracer()
    s1 = tr.span("a")
    s2 = tr.span("b", tier="x", big=1)
    assert s1 is s2                      # shared null object, no allocs
    with s1 as s:
        assert s.span_id == 0
    tr.event("e")
    tr.add("t", "x", 0.0, 1.0)
    assert tr.add_span("u", "x", 0.0, 1.0) == 0
    assert tr.snapshot() == []


def test_nesting_and_threads():
    tr = Tracer()
    tr.configure(trace_id=9)
    with tr.span("outer", tier="t") as outer:
        with tr.span("inner", tier="t"):
            tr.event("leaf", tier="t")
        assert tr._current_id() == outer.span_id

        def other():
            with tr.span("sibling", tier="t"):
                pass

        th = threading.Thread(target=other)
        th.start()
        th.join()
    spans = {s["name"]: s for s in tr.snapshot()}
    assert spans["leaf"]["parent"] == spans["inner"]["id"]
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["parent"] == 0
    # a thread with no open span must not inherit another thread's stack
    assert spans["sibling"]["parent"] == 0
    assert spans["sibling"]["tid"] != spans["outer"]["tid"]
    assert all(s["trace"] == 9 for s in spans.values())


def test_capacity_and_drop_counter():
    tr = Tracer(capacity=4)
    tr.configure(trace_id=1)
    for i in range(7):
        tr.event(f"e{i}")
    assert len(tr.snapshot()) == 4
    assert tr.dropped == 3
    assert [s["name"] for s in tr.snapshot()] == ["e3", "e4", "e5", "e6"]


def test_phase_tagging():
    tr = Tracer()
    tr.configure(trace_id=1)
    tr.set_phase("warm")
    tr.event("a")
    tr.set_phase(None)
    tr.event("b")
    a, b = tr.snapshot()
    assert a["attrs"]["phase"] == "warm" and "phase" not in b["attrs"]


# ------------------------------------------------------------ tree shape


def test_span_tree_through_search_server(pds):
    data, queries = pds
    TRACER.configure(trace_id=5)
    eng = DHNSWEngine(EngineConfig(**CFG)).build(data)
    with SearchServer(eng, BatchPolicy(max_batch=8, max_wait_s=1e-3)) as srv:
        srv.search(queries[:2], k=5)
    spans = TRACER.snapshot()
    idx = _by_id(spans)
    verbs = [s for s in spans if s["tier"] == "pool"
             and s["name"] == "pool.read_spans"]
    assert verbs, [s["name"] for s in spans]
    chain = _ancestors(verbs[-1], idx)
    # pool verb -> fetch -> round -> client search -> engine facade ->
    # serve dispatch -> serve window
    for name in ("compute.fetch", "compute.round", "compute.search",
                 "serve.dispatch", "serve.window"):
        assert name in chain, (name, chain)
    queue = [s for s in spans if s["name"] == "serve.queue"]
    assert queue and all(s["tier"] == "serve" for s in queue)


def test_spans_are_profiler_annotations(tmp_path):
    """An enabled tracer's span is a host event of the same name in a
    JAX profiler trace; a disabled tracer hands out the shared null
    span, which neither annotates nor records."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs.trace import _NULL
    tr = Tracer()
    assert tr.span("compute.plan") is _NULL
    tr.configure(trace_id=21)
    with jax.profiler.trace(str(tmp_path / "on")):
        with tr.span("compute.plan", tier="compute", rounds=2):
            with tr.span("compute.serve", tier="compute", pairs=3):
                jax.block_until_ready(jax.numpy.ones(8) * 2)
    tr.disable()
    with jax.profiler.trace(str(tmp_path / "off")):
        with tr.span("compute.route", tier="compute") as s:
            assert s is _NULL
    assert tr.snapshot() == []

    def host_events(d):
        path, = glob.glob(str(tmp_path / d / "**" / "*.xplane.pb"),
                          recursive=True)
        pd = ProfileData.from_file(path)
        return {ev.name: ev for plane in pd.planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events}

    on = host_events("on")
    assert on["compute.plan"].start_ns <= on["compute.serve"].start_ns
    assert on["compute.serve"].end_ns <= on["compute.plan"].end_ns
    assert "compute.route" not in host_events("off")


def test_compute_spans_keep_attrs_and_stats(pds):
    """Route, plan and serve are spans around the host steps: they keep
    their attributes, and the stats keep their seconds."""
    data, queries = pds
    eng = DHNSWEngine(EngineConfig(**CFG)).build(data)
    TRACER.configure(trace_id=22)
    _, _, stats = eng.search(queries[:4], k=5)
    spans = TRACER.snapshot()
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert by["compute.route"][0]["attrs"]["B"] == 4
    plan = by["compute.plan"][0]["attrs"]
    assert plan["rounds"] == stats["n_rounds"]
    assert plan["fetches"] == stats["n_fetches"]
    assert plan["hits"] == stats["cache_hits"]
    assert sum(s["attrs"]["pairs"] for s in by["compute.serve"]) \
        == stats["n_pairs"]
    assert stats["meta_s"] >= by["compute.route"][0]["dur"] > 0
    assert stats["plan_s"] >= by["compute.plan"][0]["dur"] > 0
    assert stats["sub_s"] >= sum(s["dur"] for s in by["compute.serve"]) > 0


def test_batcher_waits_are_spans(pds):
    """The dispatcher's wait for a first request and its wait while a
    window stays open for more rows are spans of their own."""
    data, queries = pds
    eng = DHNSWEngine(EngineConfig(**CFG)).build(data)
    TRACER.configure(trace_id=23)
    with SearchServer(eng, BatchPolicy(max_batch=8, max_wait_s=0.2)) as srv:
        srv.search(queries[:1], k=5)
    waits = {s["name"]: s for s in TRACER.snapshot()
             if s["name"].startswith("serve.wait_")}
    assert set(waits) == {"serve.wait_request", "serve.wait_window"}
    assert all(s["tier"] == "serve" for s in waits.values())
    # one row of eight: the window waits out most of its 0.2 s budget
    assert waits["serve.wait_window"]["dur"] > 0.1


def test_serve_and_merge_scopes_name_its_steps(built_engine, monkeypatch):
    """The serve round's compiled operations carry the serve/decode,
    serve/walk and serve/merge scopes in their op_name."""
    import jax

    from repro.core import device_store as DS
    seen = {}
    real = DS.serve_and_merge

    def spy(*args, **kw):
        seen["args"] = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                        if hasattr(a, "dtype") else a for a in args]
        seen["kw"] = kw
        return real(*args, **kw)

    monkeypatch.setattr(DS, "serve_and_merge", spy)
    rng = np.random.default_rng(0)
    built_engine.search(rng.standard_normal(
        (2, built_engine.store.spec.dim)).astype(np.float32), k=5)
    text = real.lower(*seen["args"], **seen["kw"]).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("serve/decode", "serve/walk", "serve/merge"):
        assert any(scope in n for n in names), scope


def test_compile_counter_counts_new_shapes(pds):
    """``stats()["compiles"]`` counts each program lowered: one per new
    shape of a jitted function, none for a shape seen before."""
    import jax

    from repro.obs.compiles import COMPILES
    data, queries = pds
    eng = DHNSWEngine(EngineConfig(**CFG)).build(data)
    with SearchServer(eng, BatchPolicy(max_batch=8, max_wait_s=1e-3)) as srv:
        f = jax.jit(lambda x: x * 3 + 1)
        n0 = srv.stats()["compiles"]["n"]
        f(np.ones(5, np.float32))
        assert srv.stats()["compiles"]["n"] == n0 + 1
        f(np.zeros(5, np.float32))
        f(np.ones(7, np.float32))
        assert srv.stats()["compiles"]["n"] == n0 + 2
        for _ in range(2):      # the second search finds its spans cached
            srv.search(queries[:1], k=5)
        n1 = srv.stats()["compiles"]["n"]
        srv.search(queries[:1], k=5)
        assert srv.stats()["compiles"]["n"] == n1
        snap = srv.stats()["compiles"]
    assert snap == COMPILES.snapshot() and snap["seconds"] > 0


# ------------------------------------------------------------ wire


def test_trace_flag_roundtrip_loopback(pds):
    data, queries = pds
    srv = PoolServer()
    srv.start()
    try:
        TRACER.configure(trace_id=21)
        eng = DHNSWEngine(EngineConfig(**CFG, pool="remote",
                                       endpoints=(srv.endpoint,))
                          ).build(data)
        eng.search(queries[:4], k=5)
        pool = eng.pool
        assert pool._server_trace is True     # PING capability ack
        n = pool.harvest_trace()
        assert n > 0
        spans = TRACER.snapshot()
        idx = _by_id(spans)
        server_spans = [s for s in spans if s["tier"] == "server"]
        assert len(server_spans) == n
        for s in server_spans:
            parent = idx[s["parent"]]
            assert parent["tier"] == "net"
            assert parent["name"] == "net." + s["name"][len("server."):]
            # client-side verb span covers the server service time
            assert parent["dur"] >= s["dur"] - 1e-9
            # re-based inside the parent on the client clock
            assert parent["t0"] - 1e-9 <= s["t0"]
            assert s["t0"] + s["dur"] <= parent["t0"] + parent["dur"] + 1e-9
            assert s["attrs"]["clock"] == "server"
        # drained: a second harvest only sees the previous harvest's own
        # traced STATS drain request, never a verb span twice
        n_before = len([s for s in TRACER.snapshot()
                        if s["tier"] == "server"])
        pool.harvest_trace()
        fresh = [s for s in TRACER.snapshot()
                 if s["tier"] == "server"][n_before:]
        assert all(s["name"] == "server.stats" for s in fresh)
        pool.close()
    finally:
        TRACER.disable()
        srv.stop()


def test_old_server_never_sent_trace_bytes(pds):
    data, queries = pds
    srv = PoolServer()
    srv.start()
    try:
        eng = DHNSWEngine(EngineConfig(**CFG, pool="remote",
                                       endpoints=(srv.endpoint,))
                          ).build(data)
        d0, g0, s0 = eng.search(queries[:4], k=5)
        eng.pool.close()

        TRACER.configure(trace_id=33)
        eng = DHNSWEngine(EngineConfig(**CFG, pool="remote",
                                       endpoints=(srv.endpoint,))
                          ).build(data)
        # simulate an old server: the PING ack never arrived, so the
        # client must not prefix trace context onto any frame
        eng.pool._server_trace = False
        d1, g1, s1 = eng.search(queries[:4], k=5)
        assert np.array_equal(np.asarray(d0), np.asarray(d1))
        assert np.array_equal(np.asarray(g0), np.asarray(g1))
        assert s0["net"]["bytes"] == s1["net"]["bytes"]
        assert eng.pool.harvest_trace() == 0
        assert not any(s["tier"] == "server" for s in TRACER.snapshot())
        eng.pool.close()
    finally:
        TRACER.disable()
        srv.stop()


# ------------------------------------------------------------ free-ness


def _run_combo(data, queries, pool_kind, quant, endpoints=None):
    kw = dict(CFG, pool=pool_kind, quant=quant)
    if pool_kind == "sharded":
        kw["n_shards"] = 2
    if pool_kind == "remote":
        kw["endpoints"] = endpoints
    eng = DHNSWEngine(EngineConfig(**kw)).build(data)
    d, g, st = eng.search(queries, k=5)
    out = (np.asarray(d).copy(), np.asarray(g).copy(), dict(st["net"]))
    if pool_kind == "remote":
        eng.pool.close()
    return out


@pytest.mark.parametrize("pool_kind", ["local", "sim_rdma", "sharded",
                                       "remote"])
@pytest.mark.parametrize("quant", ["none", "int8"])
def test_tracing_off_vs_on_bit_identical(pds, pool_kind, quant):
    data, queries = pds
    srv = None
    endpoints = None
    if pool_kind == "remote":
        srv = PoolServer()
        srv.start()
        endpoints = (srv.endpoint,)
    try:
        TRACER.disable()
        d0, g0, net0 = _run_combo(data, queries[:6], pool_kind, quant,
                                  endpoints)
        TRACER.configure(trace_id=11)
        d1, g1, net1 = _run_combo(data, queries[:6], pool_kind, quant,
                                  endpoints)
        assert len(TRACER.snapshot()) > 0
        assert np.array_equal(d0, d1)
        assert np.array_equal(g0, g1)
        assert net0 == net1      # ledger parity: tracing charges nothing
    finally:
        TRACER.disable()
        if srv is not None:
            srv.stop()


# ------------------------------------------------------------ exporters


def test_chrome_trace_round_trip(tmp_path):
    tr = Tracer()
    tr.configure(trace_id=3)
    with tr.span("a", tier="serve", rows=2):
        tr.event("b", tier="pool", bytes=4096.0)
    path = tmp_path / "t.json"
    assert tr.save(path) == 2
    spans = load_trace(path)
    orig = tr.snapshot()
    assert [s["name"] for s in spans] == [s["name"] for s in orig]
    assert spans[1]["attrs"]["rows"] == 2
    assert spans[0]["parent"] == spans[1]["id"]
    for a, b in zip(spans, orig):
        assert a["trace"] == b["trace"] == 3
        assert abs(a["dur"] - b["dur"]) < 1e-6
    blob = chrome_trace(orig)
    assert all(ev["ph"] == "X" for ev in blob["traceEvents"])


def test_report_names_dominant_stage(tmp_path, capsys):
    tr = Tracer()
    tr.configure(trace_id=7)
    for phase, slow in (("serial", 0.010), ("batched", 0.002)):
        tr.set_phase(phase)
        with tr.span(report.REQUEST_SPAN, tier="bench"):
            tr.add("stage.slow", "compute", 0.0, slow)
            tr.add("stage.fast", "compute", 0.0, 0.001)
    path = tmp_path / "t.json"
    tr.save(path)
    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "dominant stage" in out
    # the gap table must name the stage whose per-request self time
    # moved, not merely the biggest absolute stage
    assert "batched-vs-serial gap" in out
    assert "stage.slow" in out


def test_prometheus_renderers(pds):
    data, queries = pds
    TRACER.configure(trace_id=13)
    eng = DHNSWEngine(EngineConfig(**CFG)).build(data)
    with SearchServer(eng, BatchPolicy(max_batch=8, max_wait_s=1e-3)) as srv:
        srv.search(queries[:2], k=5)
        txt = srv.metrics_text()
    assert "# TYPE repro_serve_requests_total counter" in txt
    assert "repro_serve_requests_total 1" in txt
    assert "repro_span_seconds_bucket" in txt
    assert 'repro_pool_verbs_total{verb="read_spans"}' in txt
    assert "repro_cache_hit_ratio" in txt
    assert "repro_compiles_total" in txt
    # every exposition line parses: "name{...} value" with float value
    for line in txt.strip().splitlines():
        if line.startswith("#"):
            continue
        float(line.rsplit(" ", 1)[1])
    pool_txt = render_pool_server({"verbs": {"read_rows": 3},
                                   "service_s": {"read_rows": 0.5},
                                   "payload_rx": 10, "payload_tx": 20,
                                   "uptime_s": 1.5})
    assert 'repro_poolserver_verbs_total{verb="read_rows"} 3' in pool_txt
    assert 'repro_poolserver_payload_bytes_total{dir="rx"} 10' in pool_txt
    # renderers work with tracing off too (no histogram section)
    TRACER.disable()
    off = render_prometheus({"n_requests": 0})
    assert "repro_span_seconds" not in off


def test_dump_trace_harvests_remote(pds, tmp_path):
    data, queries = pds
    srv = PoolServer()
    srv.start()
    try:
        TRACER.configure(trace_id=17)
        eng = DHNSWEngine(EngineConfig(**CFG, pool="remote",
                                       endpoints=(srv.endpoint,))
                          ).build(data)
        with SearchServer(eng, BatchPolicy(max_batch=8,
                                           max_wait_s=1e-3)) as ss:
            ss.search(queries[:2], k=5)
            path = tmp_path / "trace.json"
            n = ss.dump_trace(path)
        spans = load_trace(path)
        assert len(spans) == n
        assert any(s["tier"] == "server" for s in spans)
        eng.pool.close()
    finally:
        TRACER.disable()
        srv.stop()


# ------------------------------------------------------------ histograms


def test_latency_histogram_unit():
    h = LatencyHistogram()
    for v in (1e-6, 1e-5, 1e-4, 1e-3):
        h.record(v)
    assert h.count == 4
    assert h.sum_s == pytest.approx(1.111e-3)
    assert h.quantile(0.5) <= h.quantile(0.99)
    assert h.quantile(1.0) >= 1e-3
    h.record(1e4)                      # overflow bucket
    assert h.quantile(1.0) > HIST_BOUNDS[-1]
    other = LatencyHistogram()
    other.record(2e-4)
    h.merge(other)
    assert h.count == 6
    assert h.mean() == pytest.approx(h.sum_s / 6)
    back = LatencyHistogram.from_dict(h.to_dict())
    assert back.counts == h.counts and back.count == h.count
    assert back.sum_s == pytest.approx(h.sum_s)


def test_verb_shard_hist_and_straggler_detector():
    vh = VerbShardHist()
    for s in range(3):
        for _ in range(40):
            vh.record("read_spans", s, 1e-2 if s == 1 else 1e-5)
    det = StragglerDetector(min_count=32)
    rep = det.verdicts(vh)
    assert set(rep["flagged"]) == {1}
    info = rep["flagged"][1]
    assert info["verb"] == "read_spans"
    assert info["excess_s"] > 1e-3
    assert info["ratio"] > det.ratio
    back = VerbShardHist.from_dict(vh.to_dict())
    assert len(back) == len(vh)
    assert back.get("read_spans", 1).count == 40
    # a uniform fleet never flags; nor does one with too few samples
    uni = VerbShardHist()
    for s in range(3):
        for _ in range(40):
            uni.record("read_rows", s, 1e-5)
    uni.record("read_meta", 0, 5.0)    # single-shard verb: no fleet
    assert det.verdicts(uni)["flagged"] == {}


# ------------------------------------------------------------ injection


def test_wr_injector_deterministic_schedule():
    a = WRInjector(seed=7, delay_s=1e-4, spike_s=1e-3, spike_every=5)
    b = WRInjector(seed=7, delay_s=1e-4, spike_s=1e-3, spike_every=5)
    for _ in range(20):
        a.on_post([None])
        b.on_post([None])
    assert a.snapshot() == b.snapshot()
    # (i * MIX + 7) % 5 == 0 <=> i % 5 == 3: posts 3, 8, 13, 18 spike
    assert a.posts == 20 and a.injections == 20
    assert a.injected_s == pytest.approx(20 * 1e-4 + 4 * 1e-3)
    c = WRInjector(seed=8, spike_s=1e-3, spike_every=5)
    for _ in range(20):
        c.on_post([None])
    assert c.injections == 4           # seed shifts which posts spike
    assert c.injected_s == pytest.approx(4e-3)


def test_wr_injector_error_is_connection_error():
    e = WRInjector(seed=0, error_every=1)
    with pytest.raises(InjectedFault):
        e.on_post([None])
    assert e.faults == 1
    assert e.injected_s == 0.0         # failed posts charge nothing
    # the fault must flow through the existing failover handlers
    assert issubclass(InjectedFault, ConnectionError)


# ------------------------------------------------------------ tail sampling


def test_tail_sampler_keeps_interesting_roots():
    tr = Tracer()
    tr.configure(trace_id=41, tail=True, tail_quantile=0.9, tail_window=64)
    for _ in range(8):                 # no stable threshold yet: kept
        with tr.span("warm", tier="serve", model_s=0.010):
            pass
    assert tr.kept == 8
    assert all(s["attrs"]["why_kept"] == "warmup" for s in tr.snapshot())
    for _ in range(10):                # under threshold: whole trace drops
        with tr.span("fast", tier="serve", model_s=0.001):
            tr.event("child", tier="pool")
    assert tr.discarded == 10
    assert len(tr.snapshot()) == 8
    with tr.span("slow", tier="serve", model_s=0.050):
        tr.event("child", tier="pool")
    spans = tr.snapshot()
    root = [s for s in spans if s["name"] == "slow"]
    assert root and root[0]["attrs"]["why_kept"] == "latency"
    assert any(s["name"] == "child" for s in spans)   # staged child kept
    with tr.span("meh", tier="serve", model_s=0.001, keep=True):
        pass
    assert tr.snapshot()[-1]["attrs"]["why_kept"] == "marked"
    with tr.span("bad", tier="serve", model_s=0.001, error=1):
        pass
    assert tr.snapshot()[-1]["attrs"]["why_kept"] == "error"
    h = tr.health()
    assert h["tail"] == 1 and h["kept"] == tr.kept == 11
    assert h["discarded"] == 10 and h["threshold_s"] > 0.0


def test_tail_sampler_default_ring_semantics_unchanged():
    # tail off: the ring is still "last N spans", as the capacity test
    # and every pre-tail consumer assume
    tr = Tracer(capacity=4)
    tr.configure(trace_id=1)
    assert tr.tail is False
    for i in range(7):
        tr.event(f"e{i}")
    assert [s["name"] for s in tr.snapshot()] == ["e3", "e4", "e5", "e6"]
    assert tr.health()["dropped"] == 3


# ------------------------------------------------------------ SLOs


def test_slo_parse_and_burn_rate():
    slo = parse_slo("p99<5ms")
    assert slo.quantile == pytest.approx(0.99)
    assert slo.threshold_s == pytest.approx(5e-3)
    assert slo.budget == pytest.approx(0.01)
    assert parse_slo("P95 < 250US").threshold_s == pytest.approx(250e-6)
    assert parse_slo(SLO(0.5, 1.0)) == SLO(0.5, 1.0)
    for bad in ("99<5ms", "p0<5ms", "p100<5ms", "p99<5min", "p99"):
        with pytest.raises(ValueError):
            parse_slo(bad)

    t = SLOTracker("p90<1ms", short_window=4, long_window=16)
    for _ in range(12):
        t.record("serve", "a", 1e-4)
    t.record("fetch", "a", 9.9)        # unconfigured tier: no-op
    r = t.report()["serve"]["a"]
    assert r["n"] == 12 and r["violations"] == 0
    assert r["burn"] == 0.0 and r["met"] is True
    for _ in range(4):                 # sustained violation
        t.record("serve", "a", 5e-3)
    r = t.report()["serve"]["a"]
    # short window all-bad: burn = 1.0 / budget(0.1); long smooths it
    assert r["burn_short"] == pytest.approx(10.0)
    assert r["burn_long"] == pytest.approx((4 / 16) / 0.1)
    assert r["burn"] == pytest.approx(2.5)   # multi-window AND: the min
    assert r["violations"] == 4 and r["met"] is False


# ------------------------------------------------------------ chaos e2e


def test_straggler_detected_and_routed_around(pds):
    data, queries = pds
    kw = dict(CFG, pool="sharded", shard_transport="sim_rdma", n_shards=3,
              replication=2, fabric=RDMA_100G)
    ref = DHNSWEngine(EngineConfig(**kw)).build(data)
    d0a, g0a, _ = ref.search(queries[:8], k=5)
    d0b, g0b, _ = ref.search(queries[8:], k=5)

    TRACER.configure(trace_id=51, tail=True, tail_window=64)
    eng = DHNSWEngine(EngineConfig(**kw)).build(data)
    eng.pool.straggler = StragglerDetector(min_count=4, min_excess_s=1e-4)
    for _ in range(3):                            # warm: healthy fleet
        d1, g1, _ = eng.search(queries[:8], k=5)
    assert eng.pool.check_stragglers()["flagged"] == {}

    inj = WRInjector(seed=7, delay_s=2e-3)
    eng.pool.children[1].set_injector(inj)
    for _ in range(3):
        d2, g2, _ = eng.search(queries[8:], k=5)
    assert inj.posts > 0
    rep = eng.pool.check_stragglers()
    assert set(rep["flagged"]) == {1}             # exactly the slow shard
    assert rep["flagged"][1]["excess_s"] >= 1e-4
    # the flagged shard loses every serving slot to a healthy replica
    assert not np.any(eng.pool._serve == 1)

    posts_before = inj.posts
    spans_before = eng.pool.verbs.get("read_spans", 0)
    d3, g3, _ = eng.search(queries[8:], k=5)
    assert eng.pool.verbs["read_spans"] > spans_before
    assert inj.posts == posts_before              # routed around shard 1

    # chaos + tail tracing never changes results
    for d, g, dr, gr in ((d1, g1, d0a, g0a), (d2, g2, d0b, g0b),
                         (d3, g3, d0b, g0b)):
        assert np.array_equal(np.asarray(d), np.asarray(dr))
        assert np.array_equal(np.asarray(g), np.asarray(gr))

    st = eng.pool.snapshot()
    assert st["stragglers"]["flagged_now"] == 1
    assert st["stragglers"]["reroutes"] >= 1
    assert st["stragglers"]["moved_groups"] >= 1
    assert st["stragglers"]["penalty_s"]["1"] >= 1e-4
    assert "read_spans" in st["hist"]


def test_slo_and_metrics_with_dead_shard(pds):
    data, queries = pds
    kw = dict(CFG, pool="sharded", shard_transport="sim_rdma", n_shards=3,
              replication=2)
    eng = DHNSWEngine(EngineConfig(**kw)).build(data)
    pol = BatchPolicy(max_batch=8, max_wait_s=1e-3, slo="p99<5ms",
                      slo_short_window=4)
    with SearchServer(eng, pol) as srv:
        srv.search(queries[:4], k=5)
        eng.pool._on_shard_down(1)
        srv.search(queries[4:8], k=5)

        # a child that dies mid-harvest is counted, never raised
        def _dead_harvest():
            raise PoolUnavailableError("shard died mid-drain")
        eng.pool.children[0].harvest_trace = _dead_harvest
        assert eng.pool.harvest_trace() == 0
        assert eng.pool.trace_harvest_failures == 1
        srv.search(queries[8:12], k=5)    # refresh the pool snapshot

        st = srv.stats()
        r = st["slo"]["serve"]["-"]
        assert r["n"] >= 2
        assert {"burn", "burn_short", "burn_long", "attainment",
                "met"} <= set(r)
        assert r["threshold_ms"] == pytest.approx(5.0)
        assert st["failover"]["trace_harvest_failures"] == 1
        assert st["failover"]["alive_shards"] == 2
        assert "stragglers" in st
        txt = srv.metrics_text()
    for family in ("repro_slo", "repro_pool_verb_latency_seconds_bucket",
                   "repro_tracer", "repro_straggler",
                   "repro_failover"):
        assert family in txt, family
    for line in txt.strip().splitlines():
        if not line.startswith("#"):
            float(line.rsplit(" ", 1)[1])


def test_pool_server_service_histograms(pds):
    data, queries = pds
    eng = DHNSWEngine(EngineConfig(**CFG, pool="remote",
                                   bearer="loopback")).build(data)
    eng.search(queries[:4], k=5)
    st = eng.pool.server_stats()
    assert st["service_hist"]
    for verb, series in st["service_hist"].items():
        assert series["count"] >= 1
        assert verb in st["service_s"]
    txt = render_pool_server(st)
    assert "repro_poolserver_service_seconds_bucket" in txt
    assert "repro_poolserver_service_seconds_count" in txt
    for line in txt.strip().splitlines():
        if not line.startswith("#"):
            float(line.rsplit(" ", 1)[1])
    eng.pool.close()


# ------------------------------------------------------------ determinism


def test_counted_pass_deterministic(sift_small):
    """Back-to-back counted passes must emit identical gated metrics —
    the contract benchmarks/perf_gate.py's serving gate stands on."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmarks"))
    try:
        import serving
    finally:
        sys.path.pop(0)
    data, queries = sift_small.data[:1200], sift_small.queries[:16]
    a = serving.counted_pass("full", data, queries, n_rep=12, C=3, k=5,
                             waves=2, seed=0)
    b = serving.counted_pass("full", data, queries, n_rep=12, C=3, k=5,
                             waves=2, seed=0)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    fused = {r["impl"]: r["mean_fused_batch"] for r in a}
    assert fused == {"serial": 1.0, "batched": 3.0}
