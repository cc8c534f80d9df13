"""The one traffic generator: reads a mix file and drives a server.

A mix is a JSON file under ``traffic/`` (keys below); a new mix is a new
file, never new code.

``loop``        ``"open"``: Poisson arrivals at ``rate_qps``, sent on
                schedule whatever the server does; ``"closed"``:
                ``outstanding`` requests in flight, each completion sends
                the next one.
``draw``        ``"zipf"`` (exponent ``zipf_s`` over the query pool, the
                ranks given to queries by a seeded permutation) or
                ``"uniform"``.
``k``           neighbours asked per query.
``rows_per_request``  query rows per request (default 1); each row is
                a query of its own, answered and judged on its own.
``on_s``, ``off_s``  open loop only, optional: arrivals come in bursts,
                ``on_s`` seconds of sending then ``off_s`` of silence,
                at ``rate_qps * (on_s + off_s) / on_s`` while on, so that
                the mean rate stays ``rate_qps``.
``warmup_s``    traffic sent before the window, counted as set-up.
``same_work``   when true, every seed sends the same requests: the query
                ids are drawn from a fixed stream and the seed only
                shuffles their order, and an open loop sends exactly
                ``rate_qps`` times the seconds of each phase, at times
                drawn from the seed (a Poisson process given its count).

Every draw comes from the run's seed, so one seed gives one sequence of
queries and arrival times.  Requests are timed from when they were due:
an open-loop request from its scheduled arrival, a closed-loop request
from the completion that freed its slot.  One sender thread drives either
loop; how late it ran is recorded per request.
"""
from __future__ import annotations

import json
import queue
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"traffic {name}: loop {mix['loop']!r}")
    if mix["draw"] not in ("zipf", "uniform"):
        raise ValueError(f"traffic {name}: draw {mix['draw']!r}")
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"traffic {name}: arrivals {mix['arrivals']!r}")
    if ("on_s" in mix) != ("off_s" in mix) or (
            "on_s" in mix and mix["loop"] != "open"):
        raise ValueError(f"traffic {name}: on_s and off_s go together, "
                         "in an open loop")
    if int(mix.get("rows_per_request", 1)) < 1:
        raise ValueError(f"traffic {name}: rows_per_request < 1")
    return mix


def query_ids(mix: dict, n_pool: int, n: int, rng,
              stream: int = 0) -> np.ndarray:
    """``n`` query-pool ids drawn as the mix says (under ``same_work``
    from fixed stream ``stream``, in an order drawn from ``rng``)."""
    if mix.get("same_work"):
        ids = _draw_ids(mix, n_pool, n, np.random.default_rng(stream))
        return ids[rng.permutation(n)]
    return _draw_ids(mix, n_pool, n, rng)


def _draw_ids(mix: dict, n_pool: int, n: int, rng) -> np.ndarray:
    if mix["draw"] == "uniform":
        return rng.integers(0, n_pool, size=n)
    ranks = np.arange(1, n_pool + 1, dtype=np.float64)
    p = ranks ** -float(mix["zipf_s"])
    p /= p.sum()
    perm = rng.permutation(n_pool)
    return perm[rng.choice(n_pool, size=n, p=p)]


def arrivals(mix: dict, seconds: float, rng) -> np.ndarray:
    """Open loop: Poisson arrival offsets (s) in ``[0, seconds)``; with
    ``on_s``/``off_s``, only inside the on phases."""
    rate = float(mix["rate_qps"])
    if "on_s" in mix:
        on, period = float(mix["on_s"]), float(mix["on_s"] + mix["off_s"])
        full, rest = divmod(seconds, period)
        on_total = full * on + min(rest, on)
        if mix.get("same_work"):
            u = np.sort(rng.random(round(rate * seconds)) * on_total)
        else:
            u = _poisson(rate * period / on, on_total, rng)
        return np.floor(u / on) * period + np.mod(u, on)
    if mix.get("same_work"):
        return np.sort(rng.random(round(rate * seconds)) * seconds)
    return _poisson(rate, seconds, rng)


def _poisson(rate: float, seconds: float, rng) -> np.ndarray:
    n = int(rate * seconds * 1.5) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=n))])
    return t[t < seconds]


@dataclass
class Log:
    """Per-request record of one drive, indexed by request number."""

    qid: list = field(default_factory=list)      # pool rows of each request
    due: list = field(default_factory=list)      # perf_counter when due
    sent: list = field(default_factory=list)     # perf_counter when sent
    done: list = field(default_factory=list)     # perf_counter, or None
    futures: list = field(default_factory=list)


def _submit(server, log: Log, pool: np.ndarray, qid: np.ndarray, k: int,
            due: float, on_done) -> None:
    i = len(log.qid)
    log.qid.append(qid)
    log.due.append(due)
    log.done.append(None)
    log.sent.append(time.perf_counter())
    fut = server.search_async(pool[qid], k)
    log.futures.append(fut)
    fut.add_done_callback(lambda f, i=i: on_done(i))


def drive(server, pool: np.ndarray, mix: dict, *, warmup_s: float,
          seconds: float, seed: int,
          on_window=None) -> tuple[Log, float, float]:
    """Send the mix for ``warmup_s`` then ``seconds``; returns the log
    and the window ``(t0, t1)`` on ``perf_counter``.  ``on_window()`` is
    called by the sender as the window opens.  Requests still in flight
    at the close are left running; ``settle`` waits for them."""
    rng = np.random.default_rng([seed, 3])
    k = int(mix["k"])
    rows = int(mix.get("rows_per_request", 1))
    log = Log()
    total = warmup_s + seconds
    if mix["loop"] == "open":
        # the warm-up and the window are drawn apart, so that under
        # ``same_work`` each holds its own fixed count
        warm = arrivals(mix, warmup_s, rng)
        win = warmup_s + arrivals(mix, seconds, rng)
        offs = np.concatenate([warm, win])
        ids = np.concatenate([
            query_ids(mix, len(pool), len(warm) * rows, rng, 0),
            query_ids(mix, len(pool), len(win) * rows, rng, 1)]
        ).reshape(-1, rows)

        def on_done(i):
            log.done[i] = time.perf_counter()

        start = time.perf_counter()
        opened = on_window is None
        for off, qid in zip(offs.tolist(), ids):
            if not opened and off >= warmup_s:
                opened = True
                on_window()
            due = start + off
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            _submit(server, log, pool, qid, k, due, on_done)
        if not opened:
            on_window()
        t0 = start + warmup_s
        end = start + total
        left = end - time.perf_counter()
        if left > 0:
            time.sleep(left)
        return log, t0, end
    # closed loop: one sender thread refills slots as completions arrive
    freed: queue.SimpleQueue = queue.SimpleQueue()

    def on_done(i):
        now = time.perf_counter()
        log.done[i] = now
        freed.put(now)

    n = int(mix["outstanding"])
    ids = iter(query_ids(mix, len(pool), (1 << 22) // rows * rows,
                         rng).reshape(-1, rows))
    start = time.perf_counter()
    for _ in range(n):
        _submit(server, log, pool, next(ids), k, start, on_done)
    end = start + total
    t0 = start + warmup_s
    opened = on_window is None
    while True:
        now = time.perf_counter()
        if not opened and now >= t0:
            opened = True
            on_window()
        left = (end if opened else t0) - now
        if left <= 0:
            break
        try:
            due = freed.get(timeout=left)
        except queue.Empty:
            continue
        if due >= end:
            break
        _submit(server, log, pool, next(ids), k, due, on_done)
    return log, t0, end


def settle(log: Log, timeout_s: float) -> None:
    """Wait up to ``timeout_s`` for every request sent to finish."""
    deadline = time.perf_counter() + timeout_s
    for fut in log.futures:
        left = deadline - time.perf_counter()
        if left <= 0:
            break
        try:
            fut.exception(timeout=left)
        except Exception:       # timed out: judged as never answered
            break
