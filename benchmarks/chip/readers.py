"""Reductions shared by the per-layer metric readers (``metrics/*.py``).

A reader takes the run's context and returns a number, or ``None`` when
it finds nothing to read; it never returns 0 for a share of a roofline.

The context (built by ``run.py`` for a ``--trace 1`` run):

``spans``         the program's spans (``repro.obs.trace``) that began in
                  the window;
``stats``         ``SearchServer.stats()`` as the window opened and as it
                  closed; ``delta(ctx, *path)`` reads the change of any
                  counter in it (``delta(ctx, "engine", "cache_hits")``);
``device_trace``  ``devtrace.reduce`` of the profiler trace of the window;
``window_s``      the window's length;
``peaks``         the ``peaks.json`` entry of the run's device kind;
``config``        the configuration.
"""
from __future__ import annotations

import numpy as np

import work

PLAN_SPANS = ("compute.route", "compute.plan", "compute.rerank_plan")


def delta(ctx: dict, *path: str) -> float:
    """Change over the window of the ``stats()`` counter at ``path``."""
    before, after = ctx["stats"]
    for key in path:
        before, after = before[key], after[key]
    return after - before


def span_durations(ctx: dict, name: str) -> list[float]:
    return [s["dur"] for s in ctx["spans"] if s["name"] == name]


def queue_wait_ms_p50(ctx: dict):
    waits = span_durations(ctx, "serve.queue")
    return float(np.median(waits)) * 1e3 if waits else None


def rows_per_call(ctx: dict):
    calls = delta(ctx, "n_fused_calls")
    return delta(ctx, "n_queries") / calls if calls else None


def plan_host_ms_per_call(ctx: dict):
    calls = delta(ctx, "n_fused_calls")
    total = sum(s["dur"] for s in ctx["spans"] if s["name"] in PLAN_SPANS)
    return total / calls * 1e3 if calls and total else None


def cache_hit_rate(ctx: dict):
    hits = delta(ctx, "engine", "cache_hits")
    n = hits + delta(ctx, "engine", "n_fetches")
    return hits / n if n else None


def fetch_kb_per_query(ctx: dict):
    rows = delta(ctx, "n_queries")
    return delta(ctx, "net", "bytes_fetched") / 1e3 / rows if rows else None


def module_ms_per_call(ctx: dict, module: str):
    """Device milliseconds of one jitted module per engine call: its
    device seconds per traced second over the window's engine calls per
    second (the trace opens just after the window does)."""
    mod = ctx["device_trace"]["modules"].get(module)
    calls = delta(ctx, "n_fused_calls")
    traced = ctx["device_trace"]["window_s"]
    if not mod or not calls or not traced:
        return None
    return mod["seconds"] / traced * ctx["window_s"] / calls * 1e3


def idle_pct(ctx: dict):
    t = ctx["device_trace"]
    if not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def stage1_roofline(ctx: dict):
    """Least time of the stage-1 work over the device time of the
    stage-1 jitted module, in percent; ``None`` without both.  The work
    of a traced module call is the mean of the calls the spans saw."""
    mod = ctx["device_trace"]["modules"].get(work.STAGE1_MODULE)
    calls = [s["attrs"] for s in ctx["spans"]
             if s["name"] == "compute.stage1_flat"]
    if not mod or not mod["seconds"] or not calls or ctx["peaks"] is None:
        return None
    cfg = ctx["config"]
    least, bound = zip(*(work.stage1_least_time(
        int(a["B"]), int(a["rows"]), cfg["dim"],
        cfg["engine"]["quant_group"], ctx["peaks"]) for a in calls))
    share = 100.0 * float(np.mean(least)) * mod["calls"] / mod["seconds"]
    print(f"stage1_roofline: bound={','.join(sorted(set(bound)))} "
          f"least_us={float(np.mean(least)) * 1e6} "
          f"device_us_per_call={mod['seconds'] / mod['calls'] * 1e6} "
          f"calls={mod['calls']}", flush=True)
    return share
