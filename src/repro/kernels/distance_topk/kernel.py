"""Fused batched L2-distance + top-k Pallas TPU kernel.

The sub-HNSW compute hot-spot restructured for the MXU: distances are a
tiled matmul (||q||^2 + ||x||^2 - 2 q.x^T, arithmetic intensity ~2D flops
per 4-byte candidate), and a running per-query top-k lives in VMEM
scratch so only k values/ids per query ever leave the kernel — never the
(B, N) distance matrix (HBM traffic drops from O(B*N) to O(B*k)).

Grid: (nq, nn), database-tile axis innermost.  Per (q-tile, x-tile):
  1. dist tile (BQ, BN) via one MXU matmul + row/col norms;
  2. bounded merge (``_merge_topk_scratch``) into the running (BQ, k)
     best, kept unsorted: count each row's tile entries below its
     current k-th best, then run as many extraction rounds as the
     largest count (at most k), each one tile-wide min + position +
     mask.  A tile that beats no row's k-th best costs one compare and
     one count;
  3. at the last x-tile, one k-round sort of the survivors (ascending,
     ties to the lower id) is written out.

Block shapes: BQ x D and BN x D with D <= 1024 -> worst-case VMEM
footprint  q(128x1024x4) + x(256x1024x4) + dist(128x256x4) + the
merge's masked copy of dist (128x256x4) + scratch (2 x 128x128x4, k
padded to a lane tile) ~= 1.8 MB, comfortably inside the ~16 MB v5e VMEM
budget; matmul dims (BQ, D, BN) are all multiples of the 128-lane MXU
tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = 3.4e38  # "worse than any real distance" sentinel (argmin-safe python float)


def _worst(best_d, best_i):
    """Each row's k-th best as (its distance (BQ, 1), a (BQ, k) mask of
    its slot): the largest distance, and among equal distances the
    largest id, which the sorted order would put last."""
    slot = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)
    worst_d = jnp.max(best_d, axis=1, keepdims=True)
    at = best_d == worst_d
    worst_i = jnp.max(jnp.where(at, best_i, -2), axis=1, keepdims=True)
    first = jnp.min(jnp.where(at & (best_i == worst_i), slot, best_d.shape[1]),
                    axis=1, keepdims=True)
    return worst_d, slot == first


def _merge_topk_scratch(best_d, best_i, tile_d, base):
    """Merge a (BQ, BN) distance tile, whose column c is row id
    ``base + c``, into the unsorted (BQ, k) running best.

    Runs one round per insertion the tile can make: the largest count,
    over the rows, of tile entries below the row's k-th best (at most
    k).  Each round takes every row's tile minimum (the lowest column
    among equal ones), puts it in place of the row's k-th best where it
    is smaller, and masks it out of the tile.  Tiles arrive in row-id
    order, so a tile entry equal to the k-th best loses to it.
    Returns (best_d, best_i, rounds).
    """
    bq, bn = tile_d.shape
    k = best_d.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    beats = tile_d < jnp.max(best_d, axis=1, keepdims=True)
    rounds = jnp.minimum(jnp.max(jnp.sum(beats.astype(jnp.int32), axis=1)), k)

    def insert(_, carry):
        best_d, best_i, tile_d = carry
        low = jnp.min(tile_d, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(tile_d == low, col, bn), axis=1, keepdims=True)
        worst_d, worst_slot = _worst(best_d, best_i)
        put = worst_slot & (low < worst_d)
        return (jnp.where(put, low, best_d), jnp.where(put, base + pos, best_i),
                jnp.where(col == pos, MASKED, tile_d))

    best_d, best_i, _ = jax.lax.fori_loop(0, rounds, insert,
                                          (best_d, best_i, tile_d))
    return best_d, best_i, rounds


def _sorted_best(best_d, best_i):
    """The running best in output order: ascending distance, ties to the
    lower id (``lax.top_k``'s order), empty slots (MASKED, -1) last."""
    k = best_d.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)
    out_d = jnp.full_like(best_d, MASKED)
    out_i = jnp.full_like(best_i, -1)
    for t in range(k):
        low = jnp.min(best_d, axis=1, keepdims=True)
        at = best_d == low
        low_i = jnp.min(jnp.where(at, best_i, jnp.iinfo(jnp.int32).max),
                        axis=1, keepdims=True)
        first = jnp.min(jnp.where(at & (best_i == low_i), slot, k),
                        axis=1, keepdims=True)
        out_d = jnp.where(slot == t, low, out_d)
        out_i = jnp.where(slot == t, low_i, out_i)
        best_d = jnp.where(slot == first, jnp.inf, best_d)
    return out_d, out_i


def _kernel(n_valid_ref, q_ref, x_ref, d_out_ref, i_out_ref,
            best_d, best_i, *, block_n: int):
    nn = pl.num_programs(1)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d, MASKED)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...].astype(jnp.float32)                   # (BQ, D)
    x = x_ref[...].astype(jnp.float32)                   # (BN, D)
    q2 = jnp.sum(q * q, axis=1, keepdims=True)           # (BQ, 1)
    x2 = jnp.sum(x * x, axis=1)[None, :]                 # (1, BN)
    dots = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dist = q2 + x2 - 2.0 * dots                          # (BQ, BN)

    base = j * block_n
    gids = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    dist = jnp.where(gids < n_valid_ref[0], dist, MASKED)

    best_d[...], best_i[...], _ = _merge_topk_scratch(
        best_d[...], best_i[...], dist, base)

    @pl.when(j == nn - 1)
    def _flush():
        d_out_ref[...], i_out_ref[...] = _sorted_best(best_d[...],
                                                       best_i[...])


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_n", "interpret"))
def distance_topk_pallas(queries, database, n_valid, *, k: int,
                         block_q: int = 128, block_n: int = 256,
                         interpret: bool = False):
    """queries (B, D) f32, database (N, D) f32, n_valid () i32.

    B % block_q == 0 and N % block_n == 0 (ops.py pads).  Returns
    ascending (dists (B, k), ids (B, k)); padded rows masked via n_valid.
    """
    bq, d = queries.shape
    n, _ = database.shape
    assert bq % block_q == 0 and n % block_n == 0, (bq, n)
    grid = (bq // block_q, n // block_n)

    kern = functools.partial(_kernel, block_n=block_n)
    d_out, i_out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_q, d), lambda i, j, nv: (i, 0)),
                pl.BlockSpec((block_n, d), lambda i, j, nv: (j, 0)),
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
    )(n_valid.reshape(1), queries, database)
    return d_out, i_out
