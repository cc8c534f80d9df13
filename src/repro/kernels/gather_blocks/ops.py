"""jit'd public wrapper for the doorbell block gather."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gather_blocks.kernel import gather_blocks_pallas
from repro.kernels.gather_blocks.ref import gather_blocks_ref


@functools.partial(jax.jit, static_argnames=("interpret", "use_ref"))
def gather_blocks(buf, block_ids, *, interpret: bool | None = None,
                  use_ref: bool = False):
    """One doorbell batch: fetch ``block_ids`` rows of ``buf`` in a single
    launch.  buf (n_blocks, blk); block_ids (m,) -> (m, blk).  Runs
    under the ``fetch/gather_spans`` scope, its name in a profiler trace."""
    with jax.named_scope("fetch/gather_spans"):
        block_ids = jnp.asarray(block_ids, jnp.int32)
        if use_ref:
            return gather_blocks_ref(buf, block_ids)
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        return gather_blocks_pallas(buf, block_ids, interpret=interpret)
