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
  2. dist tile (BQ, BN) via one MXU matmul + row/col norms; database rows
     past ``n_valid`` and query rows past ``n_live`` (the q padding) are
     masked, so padded query rows never insert and never add a round;
  3. the bounded merge of ``distance_topk`` into the unsorted (BQ, k)
     running best: as many extraction rounds as the tile has entries
     below a row's k-th best (the largest count over the rows, at most
     k), none when it has none;
  4. at the last x-tile, one sort of the k survivors (ascending, ties to
     the lower id) is written out.
The rounds the merges ran and the grid steps are summed into a (2,)
int32 SMEM output, ``work``.

VMEM: the int8 tile (BN, D) costs a quarter of its f32 twin; the
dequantized tile is transient.  Worst case with BQ=128, BN=512 (the
default: fewer, larger tiles once the merge no longer costs k rounds
each), D<=1024: q 512 KB + codes 512 KB + scales 256 KB (D//group lanes
pad to 128) + dequant 2 MB + dist, its masked copy and the column iota
3 x 256 KB + the running best 2 x 64 KB (k lanes pad to 128) ~= 4.2 MB,
~5.5 MB with the inputs double-buffered, inside the 16 MB v5e scoped
budget; matmul dims stay multiples of the 128-lane MXU tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance_topk.kernel import (MASKED, _merge_topk_scratch,
                                                _sorted_best)


def _kernel(n_valid_ref, n_live_ref, q_ref, x_ref, s_ref, d_out_ref,
            i_out_ref, work_ref, best_d, best_i, work, *, block_n: int,
            group: int):
    nq = pl.num_programs(0)
    nn = pl.num_programs(1)
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _zero():
        work[0] = 0
        work[1] = 0

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
    block_q = dist.shape[0]
    gids = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    dist = jnp.where((gids < n_valid_ref[0]) & (rows < n_live_ref[0]), dist,
                     MASKED)

    best_d[...], best_i[...], rounds = _merge_topk_scratch(
        best_d[...], best_i[...], dist, base)
    work[0] += rounds
    work[1] += 1

    @pl.when(j == nn - 1)
    def _flush():
        d_out_ref[...], i_out_ref[...] = _sorted_best(best_d[...],
                                                       best_i[...])

    @pl.when((i == nq - 1) & (j == nn - 1))
    def _report():
        work_ref[0] = work[0]
        work_ref[1] = work[1]


@functools.partial(jax.jit,
                   static_argnames=("k", "group", "block_q", "block_n",
                                    "interpret"))
def quant_topk_pallas(queries, codes, scales, n_valid, n_live, *, k: int,
                      group: int, block_q: int = 128, block_n: int = 512,
                      interpret: bool = False):
    """queries (B, D) f32, codes (N, D) int8, scales (N, D // group) f32,
    n_valid () i32, n_live () i32 (query rows past it are padding).
    B % block_q == 0 and N % block_n == 0 (ops.py pads).
    Returns ascending (dists (B, k), ids (B, k)), rows past n_valid
    masked to inf/-1, and work (2,) i32: (merge rounds, grid steps).
    """
    bq, d = queries.shape
    n, _ = codes.shape
    assert bq % block_q == 0 and n % block_n == 0, (bq, n)
    assert d % group == 0, (d, group)
    grid = (bq // block_q, n // block_n)

    kern = functools.partial(_kernel, block_n=block_n, group=group)
    d_out, i_out, work = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_q, d), lambda i, j, nv, nl: (i, 0)),
                pl.BlockSpec((block_n, d), lambda i, j, nv, nl: (j, 0)),
                pl.BlockSpec((block_n, d // group),
                             lambda i, j, nv, nl: (j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_q, k), lambda i, j, nv, nl: (i, 0)),
                pl.BlockSpec((block_q, k), lambda i, j, nv, nl: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, k), jnp.float32),
                pltpu.VMEM((block_q, k), jnp.int32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bq, k), jnp.float32),
            jax.ShapeDtypeStruct((bq, k), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ],
        interpret=interpret,
    )(n_valid.reshape(1), n_live.reshape(1), queries, codes, scales)
    return d_out, i_out, work
