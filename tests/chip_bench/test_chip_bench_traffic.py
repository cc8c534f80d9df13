"""The traffic generator and the end-to-end metric arithmetic."""
from __future__ import annotations

import math
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import stats as S    # noqa: E402
import traffic as T  # noqa: E402

MIXES = ["zipf-open80", "uniform-closed128"]


def _draw(mix, seed, n=4096):
    rng = np.random.default_rng([seed, 3])
    if mix["loop"] == "open":
        offs = T.arrivals(mix, 10.0, rng)
        return offs, T.query_ids(mix, 10_000, len(offs), rng, 1)
    return None, T.query_ids(mix, 10_000, n, rng)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_draws_other_seed_other_draws(name):
    mix = T.load(name)
    a_t, a_q = _draw(mix, 123456789012)
    b_t, b_q = _draw(mix, 123456789012)
    c_t, c_q = _draw(mix, 123456789013)
    np.testing.assert_array_equal(a_q, b_q)
    assert not np.array_equal(a_q[:200], c_q[:200])
    if a_t is not None:
        np.testing.assert_array_equal(a_t, b_t)
        assert not np.array_equal(a_t[:200], c_t[:200])


@pytest.mark.parametrize("name", MIXES)
def test_same_work_sends_the_same_requests_in_another_order(name):
    mix = dict(T.load(name), same_work=True, rate_qps=500.0)
    a_t, a_q = _draw(mix, 11)
    b_t, b_q = _draw(mix, 12)
    np.testing.assert_array_equal(np.sort(a_q), np.sort(b_q))
    assert not np.array_equal(a_q, b_q)
    if a_t is not None:
        assert len(a_t) == len(b_t) == 5000
        assert not np.array_equal(a_t, b_t)


def test_poisson_arrivals_hold_the_rate():
    mix = dict(T.load("zipf-open80"), rate_qps=1000.0)
    offs = T.arrivals(mix, 20.0, np.random.default_rng(5))
    assert offs.min() >= 0 and offs.max() < 20.0
    assert abs(len(offs) / 20.0 - 1000.0) < 4 * math.sqrt(20_000) / 20
    assert np.all(np.diff(offs) >= 0)


def test_zipf_head_share_matches_its_exponent():
    mix = T.load("zipf-open80")
    s, n = float(mix["zipf_s"]), 10_000
    ranks = np.arange(1, n + 1, dtype=np.float64) ** -s
    want = ranks[:100].sum() / ranks.sum()          # top 100 of 10,000
    ids = T.query_ids(mix, n, 200_000, np.random.default_rng(9))
    counts = np.sort(np.bincount(ids, minlength=n))[::-1]
    got = counts[:100].sum() / counts.sum()
    assert abs(got - want) < 0.01
    assert 0.45 < want < 0.6                        # about half the traffic


def test_uniform_draws_cover_the_pool_evenly():
    ids = T.query_ids(T.load("uniform-closed128"), 10_000, 200_000,
                      np.random.default_rng(2))
    counts = np.bincount(ids, minlength=10_000)
    assert counts.min() > 0 and counts.max() < 60


def test_qps_counts_only_successful_completions_inside_the_window():
    done = [0.5, 1.2, 1.9, 2.5, None, 1.5]
    ok = [True, True, True, True, False, False]
    assert S.qps(done, ok, 1.0, 2.0) == 2.0


def test_p95_is_over_all_requests_and_failures_miss_every_limit():
    due = np.zeros(100)
    done = list(np.linspace(0.001, 0.1, 100))
    ok = [True] * 100
    lat = S.latencies(due, done, ok)
    assert S.nearest_rank(lat, 0.95) == pytest.approx(0.095)
    ok[:3] = [False] * 3
    done[3:5] = [None] * 2
    lat = S.latencies(due, done, ok)
    assert np.isinf(lat[:5]).all()
    assert S.nearest_rank(lat, 0.95) == pytest.approx(0.1)
    ok[10] = False
    assert math.isinf(S.nearest_rank(S.latencies(due, done, ok), 0.95))


def test_latency_is_timed_from_due_not_sent():
    lat = S.latencies([1.0, 2.0], [1.5, 2.25], [True, True])
    np.testing.assert_allclose(lat, [0.5, 0.25])


def test_recall_counts_hits_over_k():
    truth = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    got = np.array([[4, 3, 9, -1], [5, 6, 7, 8]])
    assert S.recall(got, truth) == pytest.approx((2 / 4 + 1) / 2)



def test_bursts_send_only_while_on_and_keep_the_mean_rate():
    mix = dict(T.load("zipf-open80"), rate_qps=500.0, on_s=1.0, off_s=3.0)
    offs = T.arrivals(mix, 20.0, np.random.default_rng(7))
    assert np.all(np.mod(offs, 4.0) < 1.0) and offs.max() < 20.0
    assert np.all(np.diff(offs) >= 0)
    assert abs(len(offs) - 10_000) < 4 * math.sqrt(10_000)
    work = dict(mix, same_work=True)
    a = T.arrivals(work, 20.0, np.random.default_rng(8))
    b = T.arrivals(work, 20.0, np.random.default_rng(9))
    assert len(a) == len(b) == 10_000 and not np.array_equal(a, b)
    assert np.all(np.mod(a, 4.0) < 1.0)


def test_a_burst_without_its_pause_is_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "x.json").write_text(
        '{"loop": "open", "rate_qps": 1, "draw": "uniform", "k": 10, '
        '"warmup_s": 0, "on_s": 1}')
    monkeypatch.setattr(T, "HERE", tmp_path)
    with pytest.raises(ValueError):
        T.load("x")


class _Done:
    """A server whose every request is answered at once."""

    def search_async(self, q, k):
        from concurrent.futures import Future
        f = Future()
        f.set_result((np.zeros((len(q), k)), np.zeros((len(q), k), int), {}))
        return f


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_requests_of_several_rows_send_and_count_every_row(loop):
    mix = {"loop": loop, "rate_qps": 200.0, "outstanding": 4,
           "draw": "uniform", "k": 10, "rows_per_request": 5}
    pool = np.arange(300, dtype=np.float32)[:, None]
    log, t0, t1 = T.drive(_Done(), pool, mix, warmup_s=0.0, seconds=0.2,
                          seed=5)
    assert log.qid and all(len(q) == 5 for q in log.qid)
    n = len(log.qid)
    ok = [True] * n
    assert S.qps([1.0] * n, ok, 0.5, 1.5, rows=[5] * n) == 5.0 * n
