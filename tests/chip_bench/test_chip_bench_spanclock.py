"""The program's spans and scopes on the profiler's clock: idle time by
the innermost host span, device time by the innermost op's scope."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import devtrace   # noqa: E402
import spanclock  # noqa: E402

DATA = Path(__file__).parent / "data"


def test_innermost_labels_each_instant_by_the_shortest_open_interval():
    pieces = spanclock.innermost([(0, 10, "outer"), (2, 4, "inner"),
                                  (3, 6, "other"), (12, 13, "late")])
    assert pieces == [(0, 2, "outer"), (2, 4, "inner"), (4, 6, "other"),
                      (6, 10, "outer"), (12, 13, "late")]
    assert spanclock.innermost([]) == []


def test_idle_goes_to_the_innermost_span_on_any_thread():
    # device busy [10, 20) and [30, 40) of a window [0, 50); the
    # dispatcher waits for a request over [0, 12), then plans inside a
    # window span over [20, 30); another thread admits over [24, 26);
    # [40, 50) is under no span
    busy = [(10, 20, "x"), (30, 35, "x"), (33, 40, "x")]
    spans = [(0, 12, "serve.wait_request"), (18, 32, "serve.window"),
             (20, 28, "compute.plan"), (24, 26, "serve.admit")]
    out = spanclock.idle_split(busy, spans, (0, 50))
    assert out == {"serve.wait_request": 10, "compute.plan": 6,
                   "serve.admit": 2, "serve.window": 2, "none": 10}
    assert sum(out.values()) == 50 - 20


def test_idle_split_covers_the_whole_window_edge_to_edge():
    out = spanclock.idle_split([(-5, 3, "x"), (60, 70, "x")],
                               [(50, 55, "serve.wait_window")], (0, 60))
    assert out == {"serve.wait_window": 5, "none": 52}
    assert spanclock.idle_split([], [], (0, 7)) == {"none": 7}


def test_device_time_goes_to_the_innermost_ops_scope():
    ops = [(0, 10, "serve/walk"), (2, 5, "unscoped"), (10, 12, "serve/merge")]
    assert spanclock.scope_time(ops) == {"serve/walk": 7, "unscoped": 3,
                                         "serve/merge": 2}


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(serve_and_merge)/vmap(serve/walk)/while/body/gather:",
     "serve/walk"),
    ("jit(serve_and_merge)/vmap(serve/decode)/dynamic_slice:",
     "serve/decode"),
    ("jit(serve_and_merge)/serve/merge/jit(merge_ranked)/sort:",
     "serve/merge"),
    ("jit(_quant_topk_jit)/stage1/quant_topk/jit(quant_topk_pallas)/"
     "pallas_call:", "stage1/quant_topk"),
    ("jit(gather_blocks)/fetch/gather_spans/jit(_take)/gather:",
     "fetch/gather_spans"),
    ("jit(_quant_topk_jit)/jit(quant_topk_pallas)/pallas_call:",
     "unscoped"),
    ("jit(maximum)/max:", "unscoped"),
    ("", "unscoped")])
def test_scope_of_reads_the_programs_scope_from_tf_op(tf_op, scope):
    assert spanclock.scope_of(tf_op) == scope


@pytest.fixture(scope="module")
def pb2():
    mod = spanclock.xplane_pb2()
    if mod is None:
        pytest.skip("no xplane_pb2 installed")
    return mod


# one chip: ops [0, 4) us (serve/walk, with a 1 us body op of
# serve/decode inside), [6, 8) and [9, 10) us (unscoped); the host
# waited for a window over [4, 5) and planned over [5, 7), and JAX's own
# event spans [0, 10): idle [4, 6) is the window's and the plan's,
# [8, 9) is under no program span
TEXT = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 11 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 12 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 13 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 13 offset_ps: 9000000 duration_ps: 1000000 } }
  event_metadata { key: 11 value { id: 11 name: "while.4"
    stats { metadata_id: 7 str_value: "jit(serve_and_merge)/vmap(serve/walk)/while:" } } }
  event_metadata { key: 12 value { id: 12 name: "fusion.2"
    stats { metadata_id: 7 ref_value: 8 } } }
  event_metadata { key: 13 value { id: 13 name: "reshape.1"
    stats { metadata_id: 7 str_value: "jit(reshape)/reshape:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(serve_and_merge)/vmap(serve/decode)/copy:" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 21 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 22 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 23 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 21 value { id: 21 name: "serve.wait_window" } }
  event_metadata { key: 22 value { id: 22 name: "compute.plan" } }
  event_metadata { key: 23 value { id: 23 name: "PjitFunction(f)" } } }
'''


@pytest.fixture()
def traced(pb2, tmp_path, monkeypatch):
    from google.protobuf import text_format
    space = text_format.Parse(TEXT, pb2.XSpace())
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(space.SerializeToString())
    monkeypatch.setattr(spanclock, "TRACE_DIR", tmp_path)
    spanclock.reduce.cache_clear()
    spans = [{"name": n} for n in ("serve.wait_window", "compute.plan",
                                   "serve.queue")]
    # 10 engine calls in a 1 s window, of which 10 us were traced; the
    # device was busy 7 us of them (device.idle_pct 30%)
    return {"spans": spans, "window_s": 1.0,
            "device_trace": {"window_s": 1e-5, "busy_s": 7e-6},
            "stats": ({"n_fused_calls": 0, "compiles": {"n": 4}},
                      {"n_fused_calls": 10, "compiles": {"n": 6}})}


def test_readers_on_a_traced_window(traced, capsys):
    assert spanclock.idle_host_pct(traced) == pytest.approx(10.0)
    assert spanclock.idle_wait_window_pct(traced) == pytest.approx(10.0)
    assert spanclock.idle_pcts(traced) == pytest.approx(
        {"serve.wait_window": 10.0, "compute.plan": 10.0, "none": 10.0})
    # 3 us of serve/walk (the loop less its 1 us body op) per 10 us
    # traced: 0.3 s of a 1 s window over 10 calls is 30 ms a call
    assert spanclock.scope_ms_per_call(traced, "serve/walk") \
        == pytest.approx(30.0)
    assert spanclock.scope_ms_per_call(traced, "serve/decode") \
        == pytest.approx(10.0)
    assert spanclock.scope_ms_per_call(traced, "serve/") \
        == pytest.approx(40.0)
    assert spanclock.scope_ms_per_call(traced, "fetch/") is None
    assert spanclock.lowered_in_window(traced) == 2
    out = capsys.readouterr().out.splitlines()
    idle = dict(kv.split("=") for kv in out[0].split()[1:])
    assert out[0].startswith("idle_by_span: ") and idle.pop("unit") == "ms"
    assert {k: float(v) for k, v in idle.items()} == pytest.approx(
        {"serve.wait_window": 1e-3, "compute.plan": 1e-3, "none": 1e-3,
         "split_ms": 1e-2, "idle_pct_there": 30.0,
         "device.idle_pct": 30.0})
    unscoped, = [ln for ln in out if ln.startswith("unscoped=")]
    assert unscoped.endswith(" unit=ms_per_call")
    assert float(unscoped.split()[0][len("unscoped="):]) \
        == pytest.approx(30.0)


def test_a_program_without_annotations_or_counter_reads_nothing(traced):
    traced["spans"] = [{"name": "compute.route"}]
    del traced["stats"][0]["compiles"], traced["stats"][1]["compiles"]
    assert spanclock.idle_host_pct(traced) is None
    assert spanclock.idle_wait_window_pct(traced) is None
    assert spanclock.lowered_in_window(traced) is None
    assert spanclock.scope_ms_per_call(traced, "serve/walk") \
        == pytest.approx(30.0)


def test_a_trace_recorded_on_the_chip(pb2):
    """The recorded int8 trace predates the program's annotations and
    scopes: all of its idle time is under no span, all of its device
    time unscoped, and the two add up to its window."""
    path = str(DATA / "int8_tiny.xplane.pb.gz")
    out = spanclock.reduce(path, frozenset({"compute.plan",
                                            "compute.stage1_flat"}))
    busy = devtrace.reduce(DATA, 1.0)["busy_s"]
    assert out["idle_ps"] is None
    assert set(out["scope_ps"]) == {"unscoped"}
    assert out["scope_ps"]["unscoped"] * 1e-12 == pytest.approx(busy)
    space = spanclock.parse(path)
    ops, = spanclock.device_ops(space)
    lo, hi = spanclock.host_extent(space)
    assert lo < min(s for s, _, _ in ops) < max(e for _, e, _ in ops) < hi
    idle = spanclock.idle_split(ops, spanclock.host_spans(
        space, {"compute.plan"}), (lo, hi))
    assert set(idle) == {"none"}
    assert (idle["none"] + out["scope_ps"]["unscoped"]) \
        == pytest.approx(hi - lo)
