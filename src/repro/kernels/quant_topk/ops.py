"""jit'd public wrapper for the fused int8 dequant+distance+top-k kernel.

Pads inputs to block multiples, dispatches to the Pallas kernel
(interpret=True on CPU — this container — compiled BlockSpecs on TPU),
and restores inf/-1 padding semantics.  ``use_ref=True`` forces the
pure-jnp oracle (benchmarks A/B against it).  The jitted body runs under
the ``stage1/quant_topk`` scope, the name its operations carry in a
profiler trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.distance_topk.kernel import MASKED
from repro.kernels.distance_topk.ops import _pad_to
from repro.kernels.quant_topk.kernel import quant_topk_pallas
from repro.kernels.quant_topk.ref import quant_topk_ref


@functools.partial(jax.jit, static_argnames=("k", "group", "block_q",
                                             "block_n", "interpret",
                                             "use_ref"))
def _quant_topk_jit(queries, codes, scales, k: int, group: int, n_valid, *,
                    block_q: int, block_n: int, interpret, use_ref: bool):
    """The jitted kernel body (see ``quant_topk`` for the contract)."""
    with jax.named_scope("stage1/quant_topk"):
        if n_valid is None:
            n_valid = codes.shape[0]
        n_valid = jnp.asarray(n_valid, jnp.int32).reshape(())
        if use_ref:
            return quant_topk_ref(queries, codes, scales, k, group, n_valid)
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        B, D = queries.shape
        qp = _pad_to(queries.astype(jnp.float32), block_q, 0)
        cp = _pad_to(codes.astype(jnp.int8), block_n, 0)
        sp = _pad_to(scales.astype(jnp.float32), block_n, 0)
        d, i = quant_topk_pallas(qp, cp, sp, n_valid, k=k, group=group,
                                 block_q=block_q, block_n=block_n,
                                 interpret=interpret)
        d, i = d[:B], i[:B]
        bad = d >= MASKED * 0.99
        return jnp.where(bad, jnp.inf, d), jnp.where(bad, -1, i)


def auto_use_ref() -> bool:
    """Whether ``quant_kernel="auto"`` should take the jnp ref path.

    On backends where the Pallas kernel would run under ``interpret=True``
    (CPU — this container) the interpreter is ~an order of magnitude
    slower than the jnp oracle, so "auto" routes to the ref impl there
    and reserves Pallas for real accelerators.
    """
    return jax.default_backend() == "cpu"


def quant_topk(queries, codes, scales, k: int, group: int, n_valid=None, *,
               block_q: int = 128, block_n: int = 256,
               interpret: bool | None = None, use_ref: bool = False):
    """Top-k nearest database rows per query over an int8-quantized
    database (squared L2 on the dequantized values, ascending).

    queries (B, D) f32, codes (N, D) int8, scales (N, D // group) f32
    -> (dists (B, k), ids (B, k)).  ``n_valid`` masks padded rows.
    """
    return _quant_topk_jit(queries, codes, scales, k, group, n_valid,
                           block_q=block_q, block_n=block_n,
                           interpret=interpret, use_ref=use_ref)
