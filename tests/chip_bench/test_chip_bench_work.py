"""Stage-1 work from shapes, and the reduction of a device trace."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import harness as H  # noqa: E402
import readers       # noqa: E402
import work          # noqa: E402


def test_stage1_work_matches_a_hand_count():
    # 64 queries x 950,000 rows x 128 dims, one f32 scale per 32 codes
    nbytes, ops = work.stage1_work(64, 950_000, 128, 32)
    assert nbytes == 950_000 * 128 + 950_000 * 4 * 4 + 64 * 128 * 4
    assert nbytes == 136_832_768
    assert ops == 2 * 64 * 950_000 * 128 == 15_564_800_000


def test_stage1_is_memory_bound_on_v5e():
    peaks = H.peaks("TPU v5 lite")
    t, bound = work.stage1_least_time(64, 950_000, 128, 32, peaks)
    assert bound == "hbm"
    assert t == pytest.approx(136_832_768 / 819e9)
    # at a large enough batch the matrix unit bounds it instead
    assert work.stage1_least_time(4096, 950_000, 128, 32, peaks)[1] == \
        "compute"


def test_a_device_kind_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):
        H.peaks("TPU v4")


def test_roofline_reads_nothing_without_a_stage1_module():
    ctx = {"device_trace": {"modules": {}, "window_s": 1.0, "busy_s": 0.5},
           "spans": [], "peaks": H.peaks("TPU v5 lite"), "config": {}}
    assert readers.stage1_roofline(ctx) is None


def test_roofline_share_from_module_time():
    peaks = H.peaks("TPU v5 lite")
    least, _ = work.stage1_least_time(64, 950_000, 128, 32, peaks)
    ctx = {"device_trace": {"modules": {work.STAGE1_MODULE: {
               "seconds": 10 * 4 * least, "calls": 10}}},
           "spans": [{"name": "compute.stage1_flat",
                      "attrs": {"B": 64, "rows": 950_000}}],
           "peaks": peaks,
           "config": {"dim": 128, "engine": {"quant_group": 32}}}
    assert readers.stage1_roofline(ctx) == pytest.approx(25.0)
