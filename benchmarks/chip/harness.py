"""What every entry point of the chip benchmark shares: finding the
files of a cell by name, the chip check, the program's build and server,
shape warm-up, and counting compilations.

The program is reached through ``EngineConfig``, ``DHNSWEngine``,
``SearchServer`` and ``BatchPolicy``, and read through its spans and
``SearchServer.stats()``; shape warm-up also reads the engine's partition
representatives, cache capacity and round-padding rule
(``scheduler.pow2_pad``), since those decide which programs a window runs.
"""
from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def bench() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell(name: str, spec: dict | None = None) -> dict:
    spec = spec or bench()
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    with open(HERE / "configs" / f"{name}.json") as f:
        return json.load(f)


def peaks(device_kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def require_chip(chips: int) -> dict:
    """The device dict, or ``NoChip`` without a TPU holding ``chips``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def import_program():
    """Put the checkout's ``src`` on the path and import the entry
    points (raises ImportError where the checkout holds no program)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import DHNSWEngine, EngineConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.batcher import BatchPolicy
    from repro.serve.server import SearchServer
    return DHNSWEngine, EngineConfig, SearchServer, BatchPolicy, \
        enable_compile_cache


def build(cfg: dict, data: np.ndarray, **engine_overrides):
    DHNSWEngine, EngineConfig, *_ = import_program()
    kw = dict(cfg["engine"])
    kw.update(engine_overrides)
    return DHNSWEngine(EngineConfig(**kw)).build(data)


def server(cfg: dict, engine):
    _, _, SearchServer, BatchPolicy, _ = import_program()
    return SearchServer(engine, BatchPolicy(**cfg["policy"]))


def pow2_buckets(max_batch: int) -> list[int]:
    out, b = [], 1
    while b <= max_batch:
        out.append(b)
        b *= 2
    return out


def pow2_at_least(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def warm_fetch_widths(engine, k: int) -> None:
    """Compile every span-fetch width the partition cache can see (1 to
    its capacity): one engine call per width, of that many partition
    representatives not fetched recently, probed with ``b=1`` so that each
    row fetches exactly its own partition.  Rows are padded to a power of
    two with copies of the first, as the batcher pads."""
    reps = np.asarray(engine.meta.reps, np.float32)
    start = 0
    for width in range(1, engine.cache.capacity + 1):
        ids = (start + np.arange(width)) % len(reps)
        start += width
        pad = pow2_at_least(width) - width
        engine.search(reps[np.concatenate([ids, np.repeat(ids[:1], pad)])],
                      k=k, b=1)


def warm_batches(srv, pool: np.ndarray, k: int, max_batch: int, rng,
                 calls: int) -> None:
    """``calls`` fused calls through the server, each of a random
    power-of-two batch of random pool rows, so that the round sizes
    (pairs per round) of every batch bucket come up."""
    buckets = pow2_buckets(max_batch)
    for _ in range(calls):
        b = buckets[rng.integers(len(buckets))]
        srv.search(pool[rng.integers(0, len(pool), size=b)], k)


def warm_serve_rounds(engine, pool: np.ndarray, k: int, max_batch: int,
                      rng, max_calls: int) -> tuple[int, int]:
    """Graph tier: engine calls until every (batch bucket, padded pairs
    per round) that the serve round can take has run, as the program's
    ``compute.serve`` spans report them, or ``max_calls`` calls.  Each
    call mixes copies of a row searched just before (its partitions are
    resident: a round of hits), pool rows near it (partly resident) and
    random pool rows (fetched), in drawn proportions; it stops early
    after 64 calls that show no new shape.  Returns (shapes left
    unwarmed, calls made)."""
    from repro.core.scheduler import pow2_pad
    from repro.obs.trace import TRACER
    b = int(engine.cfg.b)
    want = {(B, pow2_pad(1) << j) for B in pow2_buckets(max_batch)
            for j in range((pow2_pad(b * B) // pow2_pad(1)).bit_length())}
    seen: set = set()
    hot = pool[rng.integers(len(pool))]
    TRACER.configure(enabled=True, capacity=4096)
    calls = stale = 0
    while want - seen and calls < max_calls and stale < 64:
        open_buckets = sorted({w[0] for w in want - seen})
        B = open_buckets[rng.integers(len(open_buckets))]
        n_near, n_cold = rng.multinomial(B, rng.dirichlet(np.ones(3)))[:2]
        near = np.argsort(np.sum(np.square(pool - hot), axis=1))[1:65]
        rows = np.concatenate([
            pool[near[rng.integers(0, len(near), n_near)]],
            pool[rng.integers(0, len(pool), n_cold)],
            np.repeat(hot[None], B - n_near - n_cold, 0)])
        TRACER.reset()
        engine.search(rows, k=k)
        new = {(B, pow2_pad(int(s["attrs"]["pairs"])))
               for s in TRACER.snapshot() if s["name"] == "compute.serve"}
        stale = 0 if new - seen else stale + 1
        seen |= new
        hot = rows[rng.integers(B)]
        calls += 1
    TRACER.disable()
    return len(want - seen), calls


class CompileCounter:
    """Counts lowerings to XLA (every compiled program not already in
    the process's memory: a true compile or a persistent-cache load),
    and keeps the name of each."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.names: list[str] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def n(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, fun_name: str = "?", **_):
        if event == self.EVENT:
            with self._lock:
                self.names.append(str(fun_name))


def memory_peak(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def log(msg: str) -> None:
    print(msg, flush=True)
