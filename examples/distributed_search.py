"""Distributed memory pool: the d-HNSW store sharded across devices.

    PYTHONPATH=src python examples/distributed_search.py

Uses 8 fake host devices (set BEFORE jax import) to stand in for the
pod: the serialized block region shards over the `model` axis (each
device = one memory instance), the meta-HNSW + metadata replicate into
every "compute instance", and a doorbell batch becomes ONE collective
launch.  Also demos straggler rebalancing and elastic rescale planning.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core import build_meta, build_store  # noqa: E402
from repro.core.distributed import ShardedStore  # noqa: E402
from repro.data.synthetic import sift_like  # noqa: E402
from repro.pool.placement import (plan_store_migration,  # noqa: E402
                                  rebalance_partitions)


def main():
    print(f"devices: {len(jax.devices())}")
    ds = sift_like(n=8000, n_queries=16, seed=0)
    meta = build_meta(ds.data, 32, seed=0)
    store = build_store(ds.data, meta)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ss = ShardedStore(store, mesh)
    print(f"store: {store.spec.n_blocks} blocks sharded over "
          f"{ss.tp} memory instances ({ss.per_shard} blocks each)")

    # one doorbell batch: fetch partitions 3, 10, 17 in ONE collective
    pids = [3, 10, 17]
    ids = np.concatenate([store.span_block_ids(p) for p in pids])
    g, v = ss.fetch(ids)
    ok = np.array_equal(np.asarray(g), store.graph_buf[ids])
    print(f"doorbell fetch of partitions {pids}: one collective launch, "
          f"{ids.size} blocks, correct={ok}")

    owners = ss.partition_owners(store)
    print(f"partition->owner map (first 12): {owners[:12].tolist()}")

    # memory instance 2 goes slow: migrate its partitions
    new_owners, moves = rebalance_partitions(owners, sick={2}, n_owners=4)
    print(f"straggler rebalance off owner 2: {len(moves)} group moves "
          f"(each a contiguous span copy)")

    # elastic rescale 4 -> 6 owners
    plan = plan_store_migration(store.spec.n_blocks, old_tp=4, new_tp=6)
    moved = sum(n for _, _, _, n in plan)
    print(f"elastic 4->6 owners: {len(plan)} contiguous moves, "
          f"{moved}/{store.spec.n_blocks} blocks relocate "
          f"({moved * store.spec.block_bytes() / 1e6:.1f} MB)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
