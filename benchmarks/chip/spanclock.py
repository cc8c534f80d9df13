"""The program's spans and device scopes, read off the profiler's clock.

While the tracer is on, each of the program's spans is also a
``jax.profiler.TraceAnnotation``: a host event of the span's name on a
``/host:`` plane of the trace, on the same clock as the device's
operations.  Each operation's metadata holds a ``tf_op`` stat, the
``op_name`` path of the jitted code it came from
(``jit(serve_and_merge)/vmap(serve/walk)/while/...:``), in which the
program's ``jax.named_scope`` segments ``<layer>/<step>`` appear.
``jax.profiler.ProfileData`` does not expose event metadata, so the
newest ``out/trace/**/*.xplane.pb`` is parsed with the ``xplane_pb2``
module the installed TensorFlow ships (loaded from its file, without
importing TensorFlow), once per process.

Two reductions:

``idle split``   each nanosecond in which no operation runs on the
                 device (busy = the union of the ``XLA Ops`` intervals,
                 as ``devtrace.py`` takes it) goes to the innermost
                 (shortest) program span open on any host thread then,
                 or to ``none``.  It is taken over the stretch that
                 both sides recorded, from the later of the first device
                 op and the first host event to the earlier of the last
                 of each: the profiler records the host for about the
                 3 s that ``run.py`` times, the device a little longer;
``scope time``   each nanosecond in which an operation runs goes to the
                 innermost operation then (a loop's body ops, not the
                 loop), and from it to the scope its ``tf_op`` names, or
                 to ``unscoped``.

The idle readers give each span its share of that idle time times
``device.idle_pct`` (``readers.idle_pct``), so that the split adds up
to the accepted metric: that divides the device's busy time, over all
it recorded, by the host-timed window.  A trace without program spans
(a program older than its annotations) or without a device plane reads
nothing.
"""
from __future__ import annotations

import functools
import glob
import gzip
import heapq
import importlib.util
import re
from pathlib import Path

import devtrace
from readers import delta, idle_pct

TRACE_DIR = Path(__file__).resolve().parent / "out" / "trace"
WAITS = ("serve.wait_request", "serve.wait_window")
NONE = "none"
UNSCOPED = "unscoped"
# the program's <layer>/<step> scope names; vmap wraps one as vmap(<scope>)
SCOPE = re.compile(r"(?<![\w.])((?:route|serve|fetch|stage1|rerank)/\w+)")


@functools.lru_cache(maxsize=1)
def xplane_pb2():
    """TensorFlow's ``xplane_pb2``, loaded from its file, or ``None``."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        return None
    path = (Path(spec.origin).parent / "tsl" / "profiler" / "protobuf"
            / "xplane_pb2.py")
    if not path.is_file():
        return None
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def newest():
    """The newest ``*.xplane.pb[.gz]`` under ``TRACE_DIR``, or ``None``."""
    paths = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb*"),
                             recursive=True))
    return paths[-1] if paths else None


def parse(path: str):
    """The ``XSpace`` in ``path`` (gzipped or not), or ``None``."""
    pb2 = xplane_pb2()
    if pb2 is None:
        return None
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = pb2.XSpace()
        space.ParseFromString(f.read())
    return space


def scope_of(tf_op: str) -> str:
    """The innermost program scope in an op's ``tf_op`` path."""
    found = SCOPE.findall(tf_op)
    return found[-1] if found else UNSCOPED


def _stat_str(plane, stat) -> str:
    if stat.str_value:
        return stat.str_value
    ref = plane.stat_metadata.get(stat.ref_value)
    return ref.name if ref is not None else ""


def _events(plane, line, label_of):
    """``(start_ps, end_ps, label)`` of ``line``'s events whose metadata
    id ``label_of`` maps to a label."""
    base = line.timestamp_ns * 1000
    out = []
    for ev in line.events:
        label = label_of.get(ev.metadata_id)
        if label is not None and ev.duration_ps > 0:
            start = base + ev.offset_ps
            out.append((start, start + ev.duration_ps, label))
    return out


def host_extent(space):
    """``(first start, last end)`` of every event on the host planes,
    or ``None`` without one."""
    lo = hi = None
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                s = base + ev.offset_ps
                e = s + ev.duration_ps
                lo = s if lo is None else min(lo, s)
                hi = e if hi is None else max(hi, e)
    return None if lo is None else (lo, hi)


def device_ops(space):
    """Per device plane, the ``(start, end, scope)`` of each operation
    (the ``XLA Modules`` line, unscoped, where a plane has no op line)."""
    chips = []
    for plane in space.planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        line = lines.get("XLA Ops") or lines.get("XLA Modules")
        if line is None:
            continue
        tf_op = next((k for k, v in plane.stat_metadata.items()
                      if v.name == "tf_op"), None)
        scopes = {}
        for key, md in plane.event_metadata.items():
            path = next((_stat_str(plane, s) for s in md.stats
                         if s.metadata_id == tf_op), "")
            scopes[key] = scope_of(path)
        chips.append(_events(plane, line, scopes))
    return chips


def host_spans(space, names) -> list:
    """``(start, end, name)`` of every host event named as one of the
    program's spans, from every host thread."""
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        label_of = {k: md.name for k, md in plane.event_metadata.items()
                    if md.name in names}
        if label_of:
            for line in plane.lines:
                out += _events(plane, line, label_of)
    return out


def innermost(intervals) -> list:
    """Sorted, disjoint ``(start, end, label)`` pieces of the union of
    ``intervals``, each labelled by the shortest interval open in it."""
    events = sorted(intervals)
    out, heap = [], []
    i, n = 0, len(events)
    t = events[0][0] if events else 0
    while i < n or heap:
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if not heap and i < n and events[i][0] > t:
            t = events[i][0]
        while i < n and events[i][0] <= t:
            s, e, label = events[i]
            if e > t:
                heapq.heappush(heap, (e - s, e, label))
            i += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if not heap:
            continue
        nxt = min(heap[0][1], events[i][0] if i < n else heap[0][1])
        if out and out[-1][1] == t and out[-1][2] == heap[0][2]:
            out[-1] = (out[-1][0], nxt, heap[0][2])
        else:
            out.append((t, nxt, heap[0][2]))
        t = nxt
    return out


def idle_split(busy, spans, window) -> dict:
    """Picoseconds of ``window`` outside every ``busy`` interval, by the
    innermost of ``spans`` open then (``none`` where none is)."""
    lo, hi = window
    gaps, t = [], lo
    for s, e in devtrace.union([(s, e) for s, e, *_ in busy]):
        if s >= hi:
            break
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    pieces = innermost(spans)
    out: dict = {}
    j = 0
    for g0, g1 in gaps:
        covered = 0
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            s, e, label = pieces[k]
            over = min(e, g1) - max(s, g0)
            if over > 0:
                out[label] = out.get(label, 0) + over
                covered += over
            k += 1
        out[NONE] = out.get(NONE, 0) + (g1 - g0 - covered)
    return out


def scope_time(ops) -> dict:
    """Picoseconds of device time by the scope of the innermost op."""
    out: dict = {}
    for s, e, scope in innermost(ops):
        out[scope] = out.get(scope, 0) + (e - s)
    return out


@functools.lru_cache(maxsize=4)
def reduce(path: str, names: frozenset):
    """Both reductions of the trace in ``path``, averaged over the
    device planes: ``{"window_ps": <length of the stretch split>,
    "idle_ps": {name: ps}, "scope_ps": {scope: ps}}``; ``idle_ps`` is
    ``None`` where no host event carries a program span's name, and the
    whole is ``None`` without a device plane or host events."""
    space = parse(path)
    if space is None:
        return None
    chips = [ops for ops in device_ops(space) if ops]
    host = host_extent(space)
    if not chips or host is None:
        return None
    spans = host_spans(space, names)
    idle: dict = {}
    scopes: dict = {}
    window = 0.0
    for ops in chips:
        lo = max(host[0], min(s for s, _, _ in ops))
        hi = min(host[1], max(e for _, e, _ in ops))
        window += (hi - lo) / len(chips)
        for name, ps in idle_split(ops, spans, (lo, hi)).items():
            idle[name] = idle.get(name, 0) + ps / len(chips)
        for scope, ps in scope_time(ops).items():
            scopes[scope] = scopes.get(scope, 0) + ps / len(chips)
    return {"window_ps": window, "idle_ps": idle if spans else None,
            "scope_ps": scopes}


def reduced(ctx: dict):
    """``reduce`` of the newest trace, with the window's span names."""
    path = newest()
    if path is None:
        return None
    return reduce(path, frozenset(s["name"] for s in ctx["spans"]))


_printed: set = set()


def _print_once(*lines: str) -> None:
    """Print ``lines`` unless this process has printed them already
    (several metrics read one reduction)."""
    if lines not in _printed:
        _printed.add(lines)
        for line in lines:
            print(line, flush=True)


def idle_pcts(ctx: dict):
    """``device.idle_pct`` split by the innermost program span open while
    the device is idle (``none`` if none), in percent of the traced
    window; prints the split in milliseconds once per trace."""
    out = reduced(ctx)
    total = idle_pct(ctx)
    if out is None or out["idle_ps"] is None or total is None:
        return None
    idle = out["idle_ps"]
    idle_ps = sum(idle.values())
    _print_once("idle_by_span: " + " ".join(
        f"{name}={ps * 1e-9}" for name, ps in sorted(
            idle.items(), key=lambda x: (x[0] == NONE, -x[1])))
        + f" split_ms={out['window_ps'] * 1e-9}"
        + f" idle_pct_there={100.0 * idle_ps / out['window_ps']}"
        + f" device.idle_pct={total} unit=ms")
    return {name: total * ps / idle_ps for name, ps in idle.items()}


def idle_host_pct(ctx: dict):
    """Idle share under host work: any program span but the waits."""
    pcts = idle_pcts(ctx)
    if pcts is None:
        return None
    return sum(v for name, v in pcts.items() if name not in WAITS + (NONE,))


def idle_wait_window_pct(ctx: dict):
    """Idle share while the batcher holds a window open for more rows."""
    pcts = idle_pcts(ctx)
    return None if pcts is None else pcts.get("serve.wait_window", 0.0)


def _ms_per_call(ctx: dict, ps: float):
    """Device milliseconds per engine call, normalised as
    ``readers.module_ms_per_call`` normalises a module's."""
    calls = delta(ctx, "n_fused_calls")
    traced = ctx["device_trace"]["window_s"]
    if not calls or not traced:
        return None
    return ps * 1e-12 / traced * ctx["window_s"] / calls * 1e3


def scope_ms_per_call(ctx: dict, prefix: str):
    """Device milliseconds per engine call of the operations whose scope
    starts with ``prefix`` (``serve/decode``, or a layer's ``fetch/``);
    ``None`` where no operation carries such a scope.  Prints every
    scope's number once per trace, and the unscoped time on a line of
    its own."""
    out = reduced(ctx)
    if out is None:
        return None
    per_call = {scope: _ms_per_call(ctx, ps)
                for scope, ps in out["scope_ps"].items()}
    if None in per_call.values():
        return None
    _print_once("device_scope_ms_per_call: " + " ".join(
        f"{scope}={ms}" for scope, ms in sorted(per_call.items())
        if scope != UNSCOPED),
        f"unscoped={per_call.get(UNSCOPED, 0.0)} unit=ms_per_call")
    mine = [ms for scope, ms in per_call.items() if scope.startswith(prefix)]
    return sum(mine) if mine else None


def lowered_in_window(ctx: dict):
    """Programs the program counted as lowered to XLA in the window."""
    try:
        return delta(ctx, "compiles", "n")
    except KeyError:
        return None
