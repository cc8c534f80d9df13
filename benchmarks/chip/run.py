#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
per-layer metrics are read by ``metrics/<metric>.py``.  One run:

1. requires a TPU with the cell's chips (else exits 2, prints no result);
2. makes the vectors and the query pool from ``--seed`` (``data.py``);
3. builds the index with the program (``DHNSWEngine.build``);
4. serves it through ``SearchServer``, warms every batch shape the
   batcher can form, then sends the mix (``traffic.py``): ``warmup_s`` of
   it as set-up, then ``--seconds`` of it as the window;
5. after the window reads the device's peak memory, frees the program,
   and judges every answer due in the window against the plain
   reference (``check.py``, ``reference.py``);
6. prints sizes, set-up phases, lateness and compilations on earlier
   lines, the compared numbers with their limits as the last lines of
   standard error, and one JSON object as the last line of standard
   output.

``--trace 1`` turns the program's spans on over the window and JAX's
profiler on over its first ``TRACE_S`` seconds, and reports the cell's
per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import threading     # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np   # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import check as C    # noqa: E402
import data as D     # noqa: E402
import devtrace      # noqa: E402
import harness as H  # noqa: E402
import reference as R  # noqa: E402
import stats as S    # noqa: E402
import traffic as T  # noqa: E402

SETTLE_S = 60.0        # how long answers due in the window are awaited
WARM_CALLS = 64        # random fused calls of set-up (harness.warm_batches)
WARM_ROUND_CALLS = 400  # most calls of harness.warm_serve_rounds
TRACE_S = 3.0          # seconds of the window a --trace 1 run profiles
TRACE_DIR = HERE / "out" / "trace"


def read_metric(name: str, ctx: dict):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float = T_START, require_chip: bool = True,
        overrides: dict | None = None, mix_overrides: dict | None = None,
        engine_hook=None, compile_cache: bool = True) -> dict:
    """One run of ``workload``; returns the result object.

    Tests run a small size on the CPU: ``require_chip=False``,
    ``overrides`` and ``mix_overrides`` replace configuration and traffic
    keys, ``engine_hook(engine)`` may wrap the built engine to plant a
    fault, and ``compile_cache=False`` leaves JAX's cache settings alone."""
    spec = H.bench()
    cell = H.cell(workload, spec)
    cfg = H.config(cell["config"])
    for key, val in (overrides or {}).items():
        if isinstance(val, dict):
            cfg[key] = dict(cfg[key], **val)
        else:
            cfg[key] = val
    mix = dict(T.load(cell["traffic"]), **(mix_overrides or {}))
    import jax
    if require_chip:
        device = H.require_chip(cell["chips"])
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": 1}
    dev = jax.devices()[0]
    *_, enable_compile_cache = H.import_program()
    H.log(f"device: {json.dumps(device)}")
    if compile_cache:
        H.log(f"compile_cache: {enable_compile_cache()}")
    compiles = H.CompileCounter()
    k = int(cfg["k"])

    t = time.perf_counter()
    data, pool = D.make(cfg, seed)
    H.log(f"setup.data: rows={len(data)} dim={data.shape[1]} "
          f"pool={len(pool)} seconds={time.perf_counter() - t}")
    t = time.perf_counter()
    engine = H.build(cfg, data)
    st = engine.store.spec
    H.log(f"setup.build: seconds={time.perf_counter() - t} "
          f"partitions={st.n_partitions} np_max={st.np_max} "
          f"staged_device_bytes={engine.pool.staging['device_bytes']}")
    if engine_hook is not None:
        engine = engine_hook(engine)
    t = time.perf_counter()
    H.warm_fetch_widths(engine, k)
    if cfg["engine"].get("search_mode") == "graph":
        left, calls = H.warm_serve_rounds(
            engine, pool, k, cfg["policy"]["max_batch"],
            np.random.default_rng([seed, 5]), max_calls=WARM_ROUND_CALLS)
        H.log(f"setup.warm_rounds: calls={calls} shapes_left={left}")
    srv = H.server(cfg, engine)
    H.warm_batches(srv, pool, k, cfg["policy"]["max_batch"],
                   np.random.default_rng([seed, 4]), calls=WARM_CALLS)
    H.log(f"setup.warm_shapes: seconds={time.perf_counter() - t} "
          f"compiles={compiles.n}")

    if trace:
        from repro.obs.trace import TRACER
        TRACER.configure(enabled=True, capacity=1 << 21)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    at_open = {}
    tracer_thread = None

    def profile():
        # the first TRACE_S of the window, without Python function
        # events: reading a whole window's trace would not end in time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        t = time.perf_counter()
        time.sleep(min(TRACE_S, seconds))
        at_open["traced_s"] = time.perf_counter() - t
        jax.profiler.stop_trace()

    def on_window():
        nonlocal tracer_thread
        at_open["compiles"] = compiles.n
        at_open["stats"] = srv.stats()
        if trace:           # started aside, so the sender keeps its pace
            tracer_thread = threading.Thread(target=profile)
            tracer_thread.start()

    log, w0, w1 = T.drive(srv, pool, mix, warmup_s=float(mix["warmup_s"]),
                          seconds=seconds, seed=seed, on_window=on_window)
    window_compiles = compiles.n - at_open["compiles"]
    if window_compiles:
        H.log("window.lowered: " + " ".join(
            compiles.names[at_open["compiles"]:]))
    at_close = srv.stats()
    traced_s = None
    if trace:
        tracer_thread.join()
        traced_s = at_open["traced_s"]
        TRACER.enabled = False
    setup_s = w0 - t_start
    T.settle(log, SETTLE_S)
    memory_peak = H.memory_peak(dev)

    due = np.asarray(log.due)
    win = np.flatnonzero((due >= w0) & (due < w1))
    ok = np.zeros(len(log.due), bool)
    answers = []                  # one (dists, ids) or None per query row
    for i in win:
        fut = log.futures[i]
        n_rows = len(log.qid[i])
        if fut.done() and fut.exception() is None:
            d, g, _ = fut.result()
            answers += list(zip(np.asarray(d), np.asarray(g)))
            ok[i] = True
        else:
            answers += [None] * n_rows
    rows_of = np.asarray([len(log.qid[i]) for i in win], np.int64)
    lat = S.latencies(due[win], [log.done[i] for i in win], ok[win])
    late = np.asarray(log.sent)[win] - due[win]
    H.log(f"window: seconds={w1 - w0} requests={len(win)} "
          f"rows={int(rows_of.sum())} answered={int(ok[win].sum())} "
          f"compiles={window_compiles} "
          f"sender_late_p99_ms={S.nearest_rank(late, .99) * 1e3} "
          f"sender_late_max_ms={float(np.max(late, initial=0)) * 1e3}")
    H.log("latency: " + " ".join(
        f"p{q}_ms={S.nearest_rank(lat, q / 100) * 1e3}" for q in (50, 90, 95, 99))
        + f" mean_finite_ms={float(np.mean(lat[np.isfinite(lat)])) * 1e3}")
    H.log(f"memory: peak_bytes_in_use={memory_peak}")
    spans = []
    if trace:
        spans = [s for s in TRACER.snapshot() if w0 <= s["t0"] < w1]
        TRACER.disable()

    # the program is freed before the reference touches the chip
    srv.stop()
    del srv, engine
    gc.collect()

    qids = (np.concatenate([log.qid[i] for i in win]) if len(win)
            else np.zeros(0, np.int64))
    uniq, inv = np.unique(qids, return_inverse=True)
    t = time.perf_counter()
    _, truth = R.exact_topk(data, pool[uniq], k)
    H.log(f"reference: queries={len(uniq)} "
          f"seconds={time.perf_counter() - t}")
    numbers = C.judge(answers, pool[qids], data, truth[inv], k,
                      cfg["correct"])
    answered = np.repeat(ok[win], rows_of)
    got = np.full((len(answers), k), -1, np.int64)
    for r, ans in enumerate(answers):
        if ans is not None:
            got[r, :len(ans[1])] = ans[1][:k]
    recall = S.recall(got[answered], truth[inv[answered]])
    H.log(f"recall: {recall}")

    result = {"correct": C.passed(numbers), "attempted": int(len(win)),
              "failed": int(len(win) - ok[win].sum())}
    device = dict(device, memory_peak_bytes=memory_peak)
    if not trace:
        values = {"qps": S.qps([log.done[i] for i in win], ok[win], w0, w1,
                               rows=rows_of),
                  "p50_ms": S.nearest_rank(lat, .5) * 1e3,
                  "p95_ms": S.nearest_rank(lat, .95) * 1e3,
                  "recall_at_10": recall, "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        metrics = {name: {"value": values[name], "unit": units[name]}
                   for name in units
                   if workload in _cells_of(spec, name, "end_to_end")}
    else:
        t = time.perf_counter()
        dtrace = devtrace.reduce(TRACE_DIR, traced_s)
        H.log(f"trace: traced_s={traced_s} "
              f"reduce_s={time.perf_counter() - t}")
        ctx = {"cell": cell, "config": cfg, "mix": mix, "spans": spans,
               "window_s": w1 - w0,
               "stats": (at_open["stats"], at_close),
               "device_trace": dtrace, "peaks": H.peaks(device["kind"])
               if require_chip else None}
        metrics = {}
        for m in spec["per_layer"]:
            if workload not in _cells_of(spec, m["name"], "per_layer"):
                continue
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=dtrace["busy_s"], window_s=dtrace["window_s"])
        result["breakdown"] = {"device_ops": dtrace["device_ops"],
                               "idle_gaps": dtrace["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = numbers
    return result


def _cells_of(spec: dict, metric: str, kind: str) -> list[str]:
    for m in spec[kind]:
        if m["name"] == metric:
            return m.get("workloads", [w["name"] for w in spec["workloads"]])
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed % (1 << 63), args.seconds,
                     bool(args.trace))
    except H.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for name, v in result["checks"].items():
        print(f"check: {name}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
