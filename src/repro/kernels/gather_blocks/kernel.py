"""Doorbell block-gather Pallas TPU kernel — the RDMA doorbell primitive.

The paper's doorbell batching posts one RDMA work request whose
descriptor list names m discontiguous remote regions; the NIC resolves
them with multiple PCIe transactions inside ONE network round trip.  The
TPU-native analogue: ONE ``pallas_call`` whose scalar-prefetched index
vector drives the input BlockSpec ``index_map``, so the same launch DMAs
m discontiguous HBM blocks into one contiguous destination.  Each grid
step's block address is known from the prefetched scalars before the
body runs — Mosaic double-buffers the HBM->VMEM streams exactly like the
NIC pipelines its PCIe reads.

Grid: (m,).  VMEM per step: 2 x blk x 4 B (in + out block), so blk up to
~256 KB keeps the double-buffered footprint well inside v5e VMEM.

Tiling: a TPU block's last two dims must be (8, 128)-divisible or equal
the array's.  A ``(1, blk)`` block of the flat ``(n_blocks, blk)`` buffer
is neither, so the launch works on a free row-major view with the block
dim squeezed: ``(n_blocks, blk // 128, 128)`` when blk is lane-aligned
(dense (8, 128) tiles), else ``(n_blocks, 1, blk)`` (last two dims equal
the array's, legal at any width).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, buf_ref, out_ref):
    out_ref[...] = buf_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks_pallas(buf, block_ids, *, interpret: bool = False):
    """buf (n_blocks, blk); block_ids (m,) i32 -> (m, blk).

    One launch = one doorbell batch: m descriptors, m HBM block reads,
    contiguous output (the compute-pool staging buffer).
    """
    m = block_ids.shape[0]
    n_blocks, blk = buf.shape
    tile = (blk // 128, 128) if blk % 128 == 0 else (1, blk)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[
                # the descriptor list: block i of the output reads remote
                # block ids[i] — data-dependent index_map via prefetch
                pl.BlockSpec((None,) + tile, lambda i, ids: (ids[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None,) + tile, lambda i, ids: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m,) + tile, buf.dtype),
        interpret=interpret,
    )(block_ids, buf.reshape((n_blocks,) + tile))
    return out.reshape(m, blk)
