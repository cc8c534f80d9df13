"""Distributed memory pool: the store sharded across a mesh axis.

The paper's memory pool is one big registered region on memory nodes; a
compute node READs blocks by remote address.  On a TPU pod we shard the
block buffers over the ``model`` axis (each chip's HBM owns
``n_blocks/tp`` contiguous blocks = one "memory instance"), replicate
the (tiny) meta-HNSW + metadata table on every chip (the paper caches
them in every compute instance), and express a doorbell fetch as ONE
collective: every owner contributes its requested blocks, ``psum``
assembles the staging buffer on all requesters.

One fetch launch == one network round trip (the paper's metric); its
wire bytes are the psum operand — the same numbers the HLO collective
parser in launch/dryrun.py counts, so the cost model and the compiled
artifact agree.

Owner mapping is block-contiguous, so a partition's span lives on one
(or two, at a boundary) owners — the layout's contiguity survives
sharding, which is what makes straggler re-balancing a contiguous copy
per group (DESIGN.md §5).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.layout import Store


def _pad_blocks(arr: np.ndarray, mult: int) -> np.ndarray:
    pad = (-arr.shape[0]) % mult
    if pad == 0:
        return arr
    return np.concatenate([arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)])


class ShardedStore:
    """Device-resident store sharded over ``axis`` of ``mesh``."""

    def __init__(self, store: Store, mesh: Mesh, axis: str = "model"):
        self.spec = store.spec
        self.mesh = mesh
        self.axis = axis
        self.tp = int(mesh.shape[axis])
        shard = NamedSharding(mesh, P(axis, None))
        g = _pad_blocks(store.graph_buf, self.tp)
        v = _pad_blocks(store.vec_buf, self.tp)
        self.n_blocks = g.shape[0]
        self.per_shard = self.n_blocks // self.tp
        self.graph_buf = jax.device_put(g, shard)
        self.vec_buf = jax.device_put(v, shard)
        # compute-pool replicas (paper: cached in every compute instance)
        rep = NamedSharding(mesh, P())
        self.meta_table = jax.device_put(store.meta_table, rep)

    # -------------------------------------------------------------- fetch

    def fetch_fn(self):
        """Returns jit'd ``fetch(graph_buf, vec_buf, block_ids) ->
        (g_blocks, v_blocks)`` — ONE collective launch per call (= one
        doorbell round trip), replicated output."""
        spec = self.spec
        per_shard = self.per_shard
        axis = self.axis

        def local_gather(buf, ids):
            lo = lax.axis_index(axis) * per_shard
            local = ids - lo
            mine = (local >= 0) & (local < per_shard)
            rows = buf[jnp.where(mine, local, 0)]
            zero = jnp.zeros((), buf.dtype)
            rows = jnp.where(mine[:, None], rows, zero)
            return lax.psum(rows, axis)

        @functools.partial(
            jax.jit,
            in_shardings=(NamedSharding(self.mesh, P(axis, None)),
                          NamedSharding(self.mesh, P(axis, None)),
                          NamedSharding(self.mesh, P())),
            out_shardings=NamedSharding(self.mesh, P()))
        def fetch(graph_buf, vec_buf, block_ids):
            gather = jax.shard_map(
                local_gather,
                mesh=self.mesh,
                in_specs=(P(axis, None), P()),
                out_specs=P(), check_vma=False)
            g = gather(graph_buf, block_ids)
            v = jax.shard_map(
                local_gather, mesh=self.mesh,
                in_specs=(P(axis, None), P()),
                out_specs=P(), check_vma=False)(vec_buf, block_ids)
            return g, v

        return fetch

    def fetch(self, block_ids: np.ndarray):
        ids = jnp.asarray(np.asarray(block_ids).reshape(-1), jnp.int32)
        g, v = self.fetch_fn()(self.graph_buf, self.vec_buf, ids)
        return g, v

    # ------------------------------------------------------- rebalancing

    def owner_of(self, block_id: int) -> int:
        return block_id // self.per_shard

    def partition_owners(self, store: Store) -> np.ndarray:
        """(P,) owner shard of each partition's span start — the
        partition->memory-instance map the heartbeat monitor rebalances."""
        starts = store.meta_table[:, 0]
        return (starts // self.per_shard).astype(np.int32)


def abstract_fetch_lowered(store: Store, mesh: Mesh, m_blocks: int,
                           axis: str = "model"):
    """Dry-run: lower+compile the fetch collective for a doorbell batch of
    ``m_blocks`` spans WITHOUT allocating the store (ShapeDtypeStructs).
    Returns (lowered, compiled)."""
    spec = store.spec
    tp = int(mesh.shape[axis])
    n_blocks = store.graph_buf.shape[0] + ((-store.graph_buf.shape[0]) % tp)
    per_shard = n_blocks // tp

    def local_gather(buf, ids):
        lo = lax.axis_index(axis) * per_shard
        local = ids - lo
        mine = (local >= 0) & (local < per_shard)
        rows = buf[jnp.where(mine, local, 0)]
        rows = jnp.where(mine[:, None], rows, jnp.zeros((), buf.dtype))
        return lax.psum(rows, axis)

    def fetch(graph_buf, vec_buf, block_ids):
        f = lambda b, i: jax.shard_map(local_gather, mesh=mesh,
                                       in_specs=(P(axis, None), P()),
                                       out_specs=P(), check_vma=False)(b, i)
        return f(graph_buf, block_ids), f(vec_buf, block_ids)

    n_ids = m_blocks * spec.fetch_blocks
    args = (jax.ShapeDtypeStruct((n_blocks, spec.gblk), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, spec.vblk), jnp.float32),
            jax.ShapeDtypeStruct((n_ids,), jnp.int32))
    with mesh:
        lowered = jax.jit(
            fetch,
            in_shardings=(NamedSharding(mesh, P(axis, None)),
                          NamedSharding(mesh, P(axis, None)),
                          NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, P())).lower(*args)
        compiled = lowered.compile()
    return lowered, compiled
