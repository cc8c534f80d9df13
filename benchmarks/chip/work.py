"""The work a kernel's call needs, from the algorithm's shapes alone.

Kept with the benchmark so that a later change to how a stage is
implemented is measured against the same work.
"""
from __future__ import annotations

# the jitted module that runs int8 stage 1 (the name the profiler gives
# ``jax.jit`` of ``_quant_topk_jit``), whatever kernel it calls inside
STAGE1_MODULE = "jit__quant_topk_jit"


def stage1_work(b: int, n: int, d: int, group: int) -> tuple[int, int]:
    """(bytes, ops) of one flat int8 stage-1 call: ``b`` f32 queries
    against ``n`` live rows of ``d`` int8 codes with one f32 scale per
    ``group`` codes, squared-L2 over every (query, row) pair."""
    nbytes = n * d + n * (d // group) * 4 + b * d * 4
    ops = 2 * b * n * d
    return nbytes, ops


def stage1_least_time(b: int, n: int, d: int, group: int,
                      peaks: dict) -> tuple[float, str]:
    """Least seconds of one call on a device with ``peaks``, and which
    term bounds it (``"hbm"`` or ``"compute"``).  The compute peak is the
    bf16 one: the codes are dequantized to floating point before the
    matrix unit."""
    nbytes, ops = stage1_work(b, n, d, group)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["bf16_flops"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
