"""Reduction of a JAX profiler trace of the window to device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``; a
test copy may be gzipped.  From the TPU planes (``/device:TPU:<n>``):

``busy_s``      seconds in which some operation ran on the device: the
                union of the intervals of the ``XLA Ops`` line (the
                ``XLA Modules`` line where a plane has no op line),
                averaged over the chips traced;
``window_s``    the length of the traced window, as the host timed it;
``modules``     per jitted module (``jit_<name>``, the profiler's
                ``(<id>)`` suffix dropped): device seconds and calls;
``device_ops``  the ten operations with the most device time, by HLO
                instruction name;
``idle_gaps``   the ten longest gaps between device operations, each
                named by the shortest host event that covers half of it
                (else the one that overlaps it most).
"""
from __future__ import annotations

import glob
import gzip
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SUFFIX = re.compile(r"\(\d+\)$")
TOP = 10


def load(trace_dir):
    """ProfileData of the newest ``*.xplane.pb[.gz]`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb*"),
                             recursive=True))
    if not paths:
        return None
    path = paths[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_name(name: str) -> str:
    return SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(trace_dir, window_s) -> dict:
    """The numbers above for the newest trace under ``trace_dir``."""
    return reduce_profile(load(trace_dir), window_s)


def reduce_profile(pd, window_s) -> dict:
    """The numbers above; zeros where the trace holds no device plane."""
    out = {"busy_s": 0.0, "window_s": float(window_s or 0.0), "modules": {},
           "device_ops": [], "idle_gaps": [], "chips": 0}
    if pd is None:
        return out
    ops_time: dict[str, float] = {}
    busy_total = 0.0
    gaps = []
    host = [(ev.start_ns, ev.end_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        out["chips"] += 1
        for ev in (lines["XLA Modules"].events
                   if "XLA Modules" in lines else ()):
            m = out["modules"].setdefault(module_name(ev.name),
                                          {"seconds": 0.0, "calls": 0})
            m["seconds"] += ev.duration_ns * 1e-9
            m["calls"] += 1
        busy_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        if busy_line is None:
            continue
        ivs = []
        for ev in busy_line.events:
            ivs.append((ev.start_ns, ev.end_ns))
            if busy_line.name == "XLA Ops":
                op = op_name(ev.name)
                ops_time[op] = ops_time.get(op, 0.0) + ev.duration_ns * 1e-9
        merged = union(ivs)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        gaps += [(b[0] - a[1], a[1], b[0])
                 for a, b in zip(merged, merged[1:])]
    if out["chips"]:
        out["busy_s"] = busy_total / out["chips"]
    out["device_ops"] = [[n, s] for n, s in sorted(
        ops_time.items(), key=lambda x: -x[1])[:TOP]]
    out["idle_gaps"] = [[_label(host, s, e), g * 1e-9]
                        for g, s, e in sorted(gaps, reverse=True)[:TOP]]
    return out


def _label(host, start: float, end: float) -> str:
    """The shortest host event covering half of ``[start, end]``, else
    the one that overlaps it most."""
    best, name = 0.0, "no host event"
    short, covering = float("inf"), None
    for s, e, n in host:
        over = min(e, end) - max(s, start)
        if over > best:
            best, name = over, n
        if over >= 0.5 * (end - start) and e - s < short:
            short, covering = e - s, n
    return covering or name
