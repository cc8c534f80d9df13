"""Per-layer readers of the engine's stage-1 counters: each reads the
counters' change over the window, and ``None`` where the program's
``stats()["engine"]`` lacks them."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))


def _reader(metric: str):
    path = CHIP / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"r_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(before: dict, after: dict) -> dict:
    return {"stats": ({"engine": before}, {"engine": after})}


OLD = {"cache_hits": 5.0, "n_fetches": 0.0, "n_rounds": 3.0}


@pytest.mark.parametrize("metric,before,after,want", [
    ("stage1.map_uploads_in_window",
     {"stage1_map_uploads": 1.0}, {"stage1_map_uploads": 1.0}, 0.0),
    ("stage1.map_uploads_in_window",
     {"stage1_map_uploads": 1.0}, {"stage1_map_uploads": 3.0}, 2.0),
    ("stage1.map_uploads_in_window", OLD, OLD, None),
    ("stage1.merge_rounds_per_tile",
     {"stage1_merge_rounds": 10.0, "stage1_tiles": 4.0},
     {"stage1_merge_rounds": 40.0, "stage1_tiles": 14.0}, 3.0),
    ("stage1.merge_rounds_per_tile",
     {"stage1_merge_rounds": 0.0, "stage1_tiles": 0.0},
     {"stage1_merge_rounds": 0.0, "stage1_tiles": 0.0}, None),
    ("stage1.merge_rounds_per_tile", OLD, OLD, None),
])
def test_stage1_counter_readers(metric, before, after, want):
    got = _reader(metric)(_ctx(before, after))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
