"""Fused int8-dequant + L2-distance + top-k Pallas TPU kernel.

The quantized-tier twin of ``kernels/distance_topk``: the database tile
arrives as int8 codes plus per-group f32 codebook scales (the wire/HBM
format of the quantized resident tier — 4x less vector traffic than
f32), is dequantized in VMEM right before the MXU matmul, and the same
running top-k scratch keeps HBM output at O(B*k).

Per (q-tile, x-tile):
  1. dequant: x = codes.f32 * scales broadcast over each group.  The
     broadcast is one (BN, D//group) x (D//group, D) matmul against a 0/1
     group-expansion matrix built from iotas: Mosaic refuses the
     lane-splitting reshape ``(BN, D) -> (BN, D//group, group)``, and the
     MXU product is exact at HIGHEST precision (one nonzero term per lane);
  2. dist tile (BQ, BN) via one MXU matmul + row/col norms;
  3. merge into the (BQ, k) running best (k unrolled argmin rounds).

VMEM: the int8 tile (BN, D) costs a quarter of its f32 twin; the
dequantized tile is transient.  Worst case with BQ=128, BN=256, D<=1024:
q 512 KB + codes 256 KB + scales 32 KB + dequant 1 MB + dist 128 KB
~= 1.9 MB, inside the ~16 MB v5e budget; matmul dims stay multiples of
the 128-lane MXU tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance_topk.kernel import MASKED, _merge_topk_scratch


def _kernel(n_valid_ref, q_ref, x_ref, s_ref, d_out_ref, i_out_ref,
            best_d, best_i, *, k: int, block_n: int, group: int):
    nn = pl.num_programs(1)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d, MASKED)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...].astype(jnp.float32)                   # (BQ, D)
    codes = x_ref[...].astype(jnp.float32)               # (BN, D) int8 -> f32
    scales = s_ref[...]                                  # (BN, D // group)
    d = codes.shape[1]
    # dequantize: expand[g, c] = 1 where lane c belongs to group g
    lane = jax.lax.broadcasted_iota(jnp.int32, (d // group, d), 1)
    grp = jax.lax.broadcasted_iota(jnp.int32, (d // group, d), 0) * group
    expand = ((lane >= grp) & (lane < grp + group)).astype(jnp.float32)
    x = codes * jnp.dot(scales, expand, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)   # (BN, D)

    q2 = jnp.sum(q * q, axis=1, keepdims=True)           # (BQ, 1)
    x2 = jnp.sum(x * x, axis=1)[None, :]                 # (1, BN)
    dots = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dist = q2 + x2 - 2.0 * dots                          # (BQ, BN)

    base = j * block_n
    gids = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    dist = jnp.where(gids < n_valid_ref[0], dist, MASKED)

    best_d[...], best_i[...] = _merge_topk_scratch(
        best_d[...], best_i[...], dist, gids, k)

    @pl.when(j == nn - 1)
    def _flush():
        d_out_ref[...] = best_d[...]
        i_out_ref[...] = best_i[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "group", "block_q", "block_n",
                                    "interpret"))
def quant_topk_pallas(queries, codes, scales, n_valid, *, k: int,
                      group: int, block_q: int = 128, block_n: int = 256,
                      interpret: bool = False):
    """queries (B, D) f32, codes (N, D) int8, scales (N, D // group) f32,
    n_valid () i32.  B % block_q == 0 and N % block_n == 0 (ops.py pads).
    Returns ascending (dists (B, k), ids (B, k)); rows past n_valid are
    masked to inf/-1.
    """
    bq, d = queries.shape
    n, _ = codes.shape
    assert bq % block_q == 0 and n % block_n == 0, (bq, n)
    assert d % group == 0, (d, group)
    grid = (bq // block_q, n // block_n)

    kern = functools.partial(_kernel, k=k, block_n=block_n, group=group)
    d_out, i_out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_q, d), lambda i, j, nv: (i, 0)),
                pl.BlockSpec((block_n, d), lambda i, j, nv: (j, 0)),
                pl.BlockSpec((block_n, d // group), lambda i, j, nv: (j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_q, k), lambda i, j, nv: (i, 0)),
                pl.BlockSpec((block_q, k), lambda i, j, nv: (i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, k), jnp.float32),
                pltpu.VMEM((block_q, k), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bq, k), jnp.float32),
            jax.ShapeDtypeStruct((bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(n_valid.reshape(1), queries, codes, scales)
    return d_out, i_out
