"""What decides ``correct``: the plain reference passes, its bfloat16
control fails, and a run with the timed path broken underneath fails."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import check as C      # noqa: E402
import control         # noqa: E402
import harness as H    # noqa: E402
import reference as R  # noqa: E402
import traffic as T    # noqa: E402

SMALL = {"rows": 4000, "queries": 400, "engine": {"n_rep": 16}}
# the graph walk at 4,000 rows misses a third of the top 10 whatever it
# probes; at 8,000 rows in 16 clusters and 32 partitions it misses 0.10
# (wrong route: 0.88), and the limit sits between, for this size only
SMALL_GRAPH = {"rows": 8000, "queries": 400, "n_clusters": 16,
               "engine": {"n_rep": 32}, "correct": {"recall_miss": 0.3}}
CELLS = ["sift1m-dhnsw-graph.zipf-open80",
         "sift1m-int8-flat.uniform-closed128"]


def _small(cell):
    return SMALL_GRAPH if "graph" in cell else SMALL


def _cfg(cell):
    cfg = H.config(H.cell(cell)["config"])
    for key, val in _small(cell).items():
        cfg[key] = dict(cfg[key], **val) if isinstance(val, dict) else val
    return cfg


@pytest.mark.parametrize("cell", CELLS)
def test_reference_passes_and_bf16_control_fails(cell):
    cfg = _cfg(cell)
    mix = dict(T.load(H.cell(cell)["traffic"]), rate_qps=200.0,
               warmup_s=0.5)
    out = control.readings(cfg, mix, seed=3000000021, seconds=2.0,
                           requests=400)
    assert out["rows"] > 100
    assert out["reference"]["correct"]
    assert out["reference"]["bad_answers"] == 0
    assert out["reference"]["dist_rel_err"] < 1e-6
    assert out["reference"]["recall_miss"] == 0.0
    assert not out["control"]["correct"]
    assert out["control"]["dist_rel_err"] > 30 * cfg["correct"]["dist_rel_err"]


def test_answerers_in_the_programs_place_read_as_the_program_does():
    # the int8 tier's planted faults and their stand-ins in the
    # program's place miss the same share of the top 10
    cell = CELLS[1]
    cfg = _cfg(cell)
    mix = dict(T.load(H.cell(cell)["traffic"]), rate_qps=200.0,
               warmup_s=0.5)
    out = control.readings(cfg, mix, seed=3000000022, seconds=2.0,
                           requests=400, in_place=("half_rows",
                                                   "stage1_4bit"),
                           program=("sound", "half_rows", "stage1_4bit"))
    assert out["program.sound"]["correct"]
    lim = cfg["correct"]["recall_miss"]
    for name in ("half_rows", "stage1_4bit"):
        placed, planted = out[name], out[f"program.{name}"]
        assert not placed["correct"] and not planted["correct"]
        assert placed["recall_miss"] > 3 * lim
        assert planted["recall_miss"] == pytest.approx(
            placed["recall_miss"], rel=0.25)


def test_malformed_answers_are_bad():
    rng = np.random.default_rng(0)
    data = rng.random((50, 8), dtype=np.float32)
    q = rng.random((5, 8), dtype=np.float32)
    d, g = R.exact_topk(data, q, 3)
    good = [(d[i], g[i]) for i in range(5)]
    limits = {"bad_answers": 0, "dist_rel_err": 1e-5, "recall_miss": 0.0}
    assert C.passed(C.judge(good, q, data, g, 3, limits))
    broken = list(good)
    broken[0] = None                                     # never answered
    broken[1] = (d[1], np.array([g[1][0], g[1][0], g[1][2]]))  # id twice
    broken[2] = (d[2][::-1].copy(), g[2][::-1].copy())   # out of order
    broken[3] = (d[3], np.array([g[3][0], g[3][1], 50]))  # outside the data
    out = C.judge(broken, q, data, g, 3, limits)
    assert out["bad_answers"]["value"] == 4 and not C.passed(out)
    assert out["recall_miss"]["value"] == 0.0     # judged on row 4 alone


def test_an_exact_answer_of_the_wrong_rows_misses_the_top_k():
    rng = np.random.default_rng(1)
    data = rng.random((50, 8), dtype=np.float32)
    q = rng.random((4, 8), dtype=np.float32)
    _, truth = R.exact_topk(data, q, 3)
    d, g = R.exact_topk(data[25:], q, 3)          # half the rows
    answers = [(d[i], g[i] + 25) for i in range(4)]
    limits = {"bad_answers": 0, "dist_rel_err": 1e-5, "recall_miss": 0.1}
    out = C.judge(answers, q, data, truth, 3, limits)
    assert out["dist_rel_err"]["value"] < 1e-6
    want = 1 - np.mean([len(set(a) & set(t)) / 3
                        for a, t in zip(g + 25, truth)])
    assert out["recall_miss"]["value"] == pytest.approx(want)


def _run(cell, hook):
    import run
    mix = {"rate_qps": 60.0, "warmup_s": 0.5, "outstanding": 16}
    return run.run(cell, 3000000031, 1.5, False, require_chip=False,
                   overrides=_small(cell), mix_overrides=mix,
                   engine_hook=hook, compile_cache=False)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [None, "answer_altered",
                                   "half_batch_left_out"],
                         ids=["sound", "answer_altered", "half_batch_left_out"])
def test_a_run_with_the_timed_path_broken_is_not_correct(cell, fault):
    res = _run(cell, fault and control.FAULTS[fault])
    assert res["attempted"] > 20
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
    spec = H.bench()
    assert set(res["metrics"]) == {
        m["name"] for m in spec["end_to_end"]
        if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell,fault", [(CELLS[0], "wrong_route"),
                                        (CELLS[1], "half_rows"),
                                        (CELLS[1], "stage1_4bit")])
def test_a_run_with_a_layer_of_the_search_broken_is_not_correct(cell, fault):
    # the distances these answers carry are exact: only the share of the
    # true top 10 they miss gives them away
    res = _run(cell, control.FAULTS[fault])
    assert not res["correct"]
    checks = res["checks"]
    assert checks["bad_answers"]["value"] == 0
    assert checks["dist_rel_err"]["value"] <= checks["dist_rel_err"]["limit"]
    assert checks["recall_miss"]["value"] > checks["recall_miss"]["limit"]


def test_a_run_of_several_rows_per_request_judges_every_row():
    import run
    mix = {"rate_qps": 20.0, "warmup_s": 0.5, "rows_per_request": 4}
    res = run.run(CELLS[0], 3000000032, 1.5, False, require_chip=False,
                  overrides=SMALL_GRAPH, mix_overrides=mix,
                  compile_cache=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 30             # same_work: 20/s for 1.5 s
    rows_done = res["metrics"]["qps"]["value"] * 1.5
    assert rows_done == pytest.approx(4 * round(rows_done / 4))
    res = run.run(CELLS[0], 3000000032, 1.5, False, require_chip=False,
                  overrides=SMALL_GRAPH, mix_overrides=mix,
                  engine_hook=control.FAULTS["half_batch_left_out"],
                  compile_cache=False)
    assert not res["correct"]
