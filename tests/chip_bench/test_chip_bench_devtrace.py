"""The reduction from a profiler trace to device numbers."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import readers   # noqa: E402

# two chips' worth of planes: device ops at [0, 2) and [3, 5) us on
# TPU:0, [0, 1) us on TPU:1; the host ran one call across TPU:0's gap
TEXT = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 12 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 12 offset_ps: 3000000 duration_ps: 2000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_serve_and_merge(7)" } }
  event_metadata { key: 11 value { id: 11 name: "fusion.1" } }
  event_metadata { key: 12 value { id: 12 name: "custom-call.2" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 11 value { id: 11 name: "fusion.1" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 20 offset_ps: 1500000 duration_ps: 2000000 }
    events { metadata_id: 21 offset_ps: 0 duration_ps: 300000 } }
  event_metadata { key: 20 value { id: 20 name: "PjitFunction(plan)" } }
  event_metadata { key: 21 value { id: 21 name: "other" } } }
'''


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return devtrace.reduce_profile(ProfileData.from_text_proto(TEXT), 1e-5)


def test_busy_is_the_union_of_op_intervals_averaged_over_chips(reduced):
    assert reduced["chips"] == 2
    assert reduced["busy_s"] == pytest.approx((4e-6 + 1e-6) / 2)
    assert reduced["window_s"] == 1e-5


def test_modules_lose_their_id_suffix(reduced):
    assert reduced["modules"] == {
        "jit_serve_and_merge": {"seconds": pytest.approx(5e-6), "calls": 1}}


def test_ops_and_gaps_are_ranked_and_named(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert names == ["fusion.1", "custom-call.2"]
    assert reduced["device_ops"][0][1] == pytest.approx(3e-6)
    assert reduced["idle_gaps"] == [["PjitFunction(plan)",
                                     pytest.approx(1e-6)]]


def test_idle_share_reads_busy_over_window(reduced):
    ctx = {"device_trace": reduced}
    assert readers.idle_pct(ctx) == pytest.approx(75.0)


def test_no_trace_reads_nothing(tmp_path):
    out = devtrace.reduce(tmp_path, 1.0)
    assert out["busy_s"] == 0.0 and out["chips"] == 0
    assert readers.idle_pct({"device_trace": out}) is None


def test_a_trace_recorded_on_the_chip():
    """Three int8 engine calls under a closed loop, traced on a TPU v5e:
    64-row batches over 950,000 live rows."""
    import work
    out = devtrace.reduce(Path(__file__).parent / "data", 0.1)
    assert out["chips"] == 1
    mod = out["modules"][work.STAGE1_MODULE]
    assert mod["calls"] == 3 and 0 < mod["seconds"] < 1
    assert 0 < out["busy_s"] < 1
    assert any(name.startswith("quant_topk_pallas")
               for name, _ in out["device_ops"])
    assert all(" = " not in name for name, _ in out["device_ops"])
    assert out["idle_gaps"] and all(g > 0 for _, g in out["idle_gaps"])
    spans = [{"name": "compute.stage1_flat",
              "attrs": {"B": 64, "rows": 950_000}}]
    import harness as H
    share = readers.stage1_roofline({
        "device_trace": out, "spans": spans,
        "peaks": H.peaks("TPU v5 lite"),
        "config": {"dim": 128, "engine": {"quant_group": 32}}})
    assert 0 < share < 100
