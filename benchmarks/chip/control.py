#!/usr/bin/env python3
"""The control and the planted faults: each must come out not correct.

    python benchmarks/chip/control.py --workload <cell> --seeds 11,12,13 \
        [--in-place control,reference,half_rows,stage1_4bit] \
        [--program sound,b_halved,wrong_route] [--requests N]

For each seed it makes the cell's vectors and query pool, draws the query
rows of one window of the cell's traffic (``--requests`` requests for a
closed loop, whose count depends on the server; an open loop's come from
its schedule), answers them, and judges the answers with ``check.judge``
exactly as a run judges the program's.  One JSON line per seed, with the
numbers of every answerer.

Answerers put in the program's place (no build):

``control``      the plain reference one precision lower (bfloat16);
``reference``    the plain reference itself, which must pass;
``half_rows``    the exact search over the first half of the rows only;
``stage1_4bit``  stage 1 over int8 codes rounded to 4 bits (16 levels),
                 then the exact re-rank of ``2k`` candidates.

With ``--program`` it also builds the program once per seed and answers
the same rows through ``DHNSWEngine.search`` in batches of the cell's
``max_batch``, sound (``sound``) and with each named fault of ``FAULTS``
planted.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import check as C      # noqa: E402
import data as D       # noqa: E402
import harness as H    # noqa: E402
import reference as R  # noqa: E402
import traffic as T    # noqa: E402


# ------------------------------------------------ faults planted in the program

def _b_halved(engine):
    """Every search probes half the partitions the configuration says."""
    search = engine.search

    def faulty(queries, k=10, **kw):
        kw["b"] = max(1, (kw.get("b") or engine.cfg.b) // 2)
        return search(queries, k=k, **kw)
    engine.search = faulty
    return engine


def _wrong_route(engine):
    """The meta route hands every query partitions half the id space
    away from the ones it chose."""
    client = engine.client
    route = client._route
    n = engine.store.spec.n_partitions

    def faulty(q_dev, b):
        pids = route(q_dev, b)
        return np.where(pids >= 0, (pids + n // 2) % n, pids)
    client._route = faulty
    return engine


def _flat_fault(change):
    """A fault in the int8 tier's flat stage 1, planted as it syncs."""
    def plant(engine):
        client = engine.client
        sync = client._sync_flat

        def faulty(ledger):
            sync(ledger)
            change(client)
        client._sync_flat = faulty
        client._flat_synced = False       # the next search syncs anew
        return engine
    return plant


def _scan_half(client):
    client._flat_n //= 2


def _codes_to_4bit(client):
    import jax.numpy as jnp
    c = client._flat_codes.astype(jnp.float32)
    client._flat_codes = (jnp.clip(jnp.round(c / 16), -8, 7) * 16
                          ).astype(jnp.int8)


def _answer_altered(engine):
    """The first answer of every call names another row."""
    search = engine.search

    def faulty(queries, k=10, **kw):
        d, g, st = search(queries, k=k, **kw)
        g = np.array(g)
        g[0, 0] = (g[0, 0] + 1) % engine.client._n0
        return d, g, st
    engine.search = faulty
    return engine


def _half_batch_left_out(engine):
    """Each call searches the first half of its rows and hands those
    answers round to the rest."""
    search = engine.search

    def faulty(queries, k=10, **kw):
        half = max(1, len(queries) // 2)
        d, g, st = search(queries[:half], k=k, **kw)
        reps = -(-len(queries) // half)
        return (np.tile(d, (reps, 1))[:len(queries)],
                np.tile(g, (reps, 1))[:len(queries)], st)
    engine.search = faulty
    return engine


FAULTS = {"b_halved": _b_halved, "wrong_route": _wrong_route,
          "half_rows": _flat_fault(_scan_half),
          "stage1_4bit": _flat_fault(_codes_to_4bit),
          "answer_altered": _answer_altered,
          "half_batch_left_out": _half_batch_left_out}


# ------------------------------------------- answerers in the program's place

def _half_rows_topk(data, queries, k):
    return R.exact_topk(data[:len(data) // 2], queries, k)


def _stage1_4bit_topk(data, queries, k, group=32):
    """Candidates by the distance to rows decoded from symmetric int8
    codes per group rounded to 4 bits, then the exact re-rank."""
    n, dim = data.shape
    g = data.reshape(n, dim // group, group)
    scale = np.maximum(np.abs(g).max(axis=-1, keepdims=True), 1e-30) / 127
    codes = np.clip(np.round(g / scale), -127, 127)
    codes = np.clip(np.round(codes / 16), -8, 7) * 16
    decoded = (codes * scale).reshape(n, dim).astype(np.float32)
    m = 2 * k
    _, cand = R.exact_topk(decoded, queries, m)
    true = R.true_dists(data, queries, cand)
    order = np.argsort(true, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(true, order, 1),
            np.take_along_axis(cand, order, 1))


IN_PLACE = {"control": R.control_topk, "reference": R.exact_topk,
            "half_rows": _half_rows_topk, "stage1_4bit": _stage1_4bit_topk}


def window_qids(mix: dict, n_pool: int, seconds: float, seed: int,
                requests: int) -> np.ndarray:
    """Query rows of one window of ``mix``, drawn as ``traffic.drive``
    draws them."""
    rng = np.random.default_rng([seed, 3])
    rows = int(mix.get("rows_per_request", 1))
    warm = float(mix["warmup_s"])
    if mix["loop"] == "open":
        n_warm = len(T.arrivals(mix, warm, rng))
        n_win = len(T.arrivals(mix, seconds, rng))
        T.query_ids(mix, n_pool, n_warm * rows, rng, 0)
        return T.query_ids(mix, n_pool, n_win * rows, rng, 1)
    return T.query_ids(mix, n_pool, requests * rows, rng)


def _program_answers(engine, queries, k, max_batch):
    out_d, out_g = [], []
    for s in range(0, len(queries), max_batch):
        qb = queries[s:s + max_batch]
        pad = H.pow2_at_least(len(qb)) - len(qb)
        d, g, _ = engine.search(np.concatenate([qb, np.repeat(qb[:1], pad, 0)]),
                                k=k)
        out_d.append(np.asarray(d)[:len(qb)])
        out_g.append(np.asarray(g)[:len(qb)])
    return np.concatenate(out_d), np.concatenate(out_g)


def readings(cfg: dict, mix: dict, seed: int, seconds: float,
             requests: int, in_place=("control", "reference"),
             program=()) -> dict:
    data, pool = D.make(cfg, seed)
    qids = window_qids(mix, len(pool), seconds, seed, requests)
    uniq, inv = np.unique(qids, return_inverse=True)
    k = int(cfg["k"])
    _, truth = R.exact_topk(data, pool[uniq], k)
    out = {"seed": seed, "rows": int(len(qids))}

    def judge(name, d, g):
        answers = [(d[u], g[u]) for u in inv]
        numbers = C.judge(answers, pool[qids], data, truth[inv], k,
                          cfg["correct"])
        out[name] = {"correct": C.passed(numbers),
                     **{n: v["value"] for n, v in numbers.items()}}

    for name in in_place:
        judge(name, *IN_PLACE[name](data, pool[uniq], k))
    if program:
        engine = H.build(cfg, data)
        import jax
        print(f"build: np_max={engine.store.spec.np_max} "
              f"peak_bytes={H.memory_peak(jax.devices()[0])}",
              file=sys.stderr, flush=True)
        for name in program:
            own = set(engine.__dict__), set(engine.client.__dict__)
            eng = engine if name == "sound" else FAULTS[name](engine)
            judge(f"program.{name}", *_program_answers(
                eng, pool[uniq], k, int(cfg["policy"]["max_batch"])))
            # take the planted wrappers out again; a flat view a fault
            # changed is made anew by the next search
            for obj, keys in zip((engine, engine.client), own):
                for key in set(obj.__dict__) - keys:
                    del obj.__dict__[key]
            engine.client._flat_synced = False
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window length (default: BENCHMARK.json run_seconds)")
    ap.add_argument("--requests", type=int, default=100_000)
    ap.add_argument("--in-place", default="control,reference")
    ap.add_argument("--program", default="")
    args = ap.parse_args(argv)
    spec = H.bench()
    cell = H.cell(args.workload, spec)
    H.require_chip(cell["chips"])
    *_, enable_compile_cache = H.import_program()
    enable_compile_cache()
    cfg = H.config(cell["config"])
    mix = T.load(cell["traffic"])
    seconds = args.seconds or float(spec["run_seconds"])
    split = (lambda s: tuple(x for x in s.split(",") if x))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cfg, mix, seed, seconds, args.requests,
                                  split(args.in_place), split(args.program))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
