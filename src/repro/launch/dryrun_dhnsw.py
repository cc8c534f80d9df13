"""Multi-pod dry-run for the d-HNSW serving step itself.

Lowers + compiles the distributed fetch+serve step (the paper's
technique) at SIFT1M scale on the production meshes, WITHOUT allocating
the store (ShapeDtypeStructs only), and reports the roofline terms from
the compiled artifact — the "most representative of the paper" cell of
the §Perf hillclimb.

Step under test (one batch round, steady state):
  1. doorbell fetch: m partition spans gathered from the sharded block
     region (one collective);
  2. decode + MXU distance/top-k over the fetched partitions for the
     round's (query, partition) pairs;
  3. per-query top-k merge.

Variants (--variant):
  baseline   — paper-faithful mapping: store sharded over `model`, psum
               fetch replicated to every compute instance.
  sharded    — beyond-paper: queries/pairs sharded over `data`; each
               replica psums only ITS round's spans (wire / data-degree).
  quantized  — + int8 wire format for the vector payload (4x fewer
               bytes on the fetch collective; dequantized on arrival).
  int8_rest  — + the store itself holds int8 vectors (quantized once at
               build, not per fetch): kills the per-launch full-shard
               quantize pass AND shrinks the memory-pool footprint 4x.
  span_dma   — + fetch each span with ONE contiguous dynamic-slice DMA
               instead of a row gather (the paper's layout guarantee:
               a partition + its overflow is one contiguous read; shard
               boundaries are group-aligned so spans never straddle
               owners).  Row-gather HLO charges the whole operand in
               bytes-accessed; contiguous slices touch only the spans.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.launch.dryrun import parse_collectives  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

# SIFT1M-scale store geometry (paper: 1M x 128d, 500 partitions)
DIM = 128
DEG = 16
NP_MAX = 2_560            # ~1M/500 padded
OV_CAP = 512
SLOT_VECS = 64
N_PARTS = 500
M_FETCH = 16              # spans per doorbell batch (per compute replica)
PAIRS = 64                # (query, partition) pairs served per round
K = 10

GBLK = SLOT_VECS * (DEG + 1)
VBLK = SLOT_VECS * DIM
DATA_BLOCKS = -(-NP_MAX * (DEG + 1) // GBLK)
_DB_V = -(-NP_MAX * DIM // VBLK)
DATA_BLOCKS = max(DATA_BLOCKS, _DB_V)
OV_BLOCKS = max(-(-OV_CAP // GBLK), -(-OV_CAP * DIM // VBLK))
FETCH_BLOCKS = DATA_BLOCKS + OV_BLOCKS
N_BLOCKS = ((N_PARTS + 1) // 2) * (2 * DATA_BLOCKS + OV_BLOCKS)


def make_step(mesh, variant: str):
    axis = "model"
    tp = int(mesh.shape[axis])
    n_blocks = N_BLOCKS + ((-N_BLOCKS) % tp)
    per_shard = n_blocks // tp
    if variant in ("span_dma", "bf16_serve"):
        # group-align the shard boundary so no fetch span straddles two
        # memory owners (production build rule; costs <1 group of pad)
        group_blocks = 2 * DATA_BLOCKS + OV_BLOCKS
        per_shard = -(-per_shard // group_blocks) * group_blocks
        n_blocks = per_shard * tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def local_gather(buf, ids, zero):
        lo = lax.axis_index(axis) * per_shard
        local = ids - lo
        mine = (local >= 0) & (local < per_shard)
        rows = buf[jnp.where(mine, local, 0)]
        rows = jnp.where(mine[:, None], rows, zero)
        return lax.psum(rows, axis)

    def serve(v_rows, queries, pair_valid, dtype=jnp.float32):
        # v_rows: (PAIRS, FETCH_BLOCKS*VBLK) fetched spans
        vecs = v_rows[:, : NP_MAX * DIM].reshape(PAIRS, NP_MAX, DIM)
        vecs = vecs.astype(dtype)
        qd = queries.astype(dtype)
        q2 = jnp.sum(qd.astype(jnp.float32) ** 2, -1)[:, None]
        x2 = jnp.sum(vecs.astype(jnp.float32) ** 2, -1)
        dots = jax.lax.dot_general(
            qd, vecs, (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        dist = q2 + x2 - 2.0 * dots
        dist = jnp.where(pair_valid[:, None], dist, jnp.inf)
        nd, ni = lax.top_k(-dist, K)
        return -nd, ni

    if variant == "baseline":
        # replicated fetch: every chip receives every span (paper's
        # "cache in each compute instance" done naively on-pod)
        def step(vec_buf, block_ids, queries, pair_slot, pair_valid):
            v = jax.shard_map(
                lambda b, i: local_gather(b, i, jnp.zeros((), b.dtype)),
                mesh=mesh, in_specs=(P(axis, None), P()),
                out_specs=P(), check_vma=False)(vec_buf, block_ids)
            rows = v.reshape(M_FETCH, -1)[pair_slot]
            return serve(rows, queries, pair_valid)

        specs = dict(
            vec=jax.ShapeDtypeStruct((n_blocks, VBLK), jnp.float32),
            ids=jax.ShapeDtypeStruct((M_FETCH * FETCH_BLOCKS,), jnp.int32),
            q=jax.ShapeDtypeStruct((PAIRS, DIM), jnp.float32),
            slot=jax.ShapeDtypeStruct((PAIRS,), jnp.int32),
            valid=jax.ShapeDtypeStruct((PAIRS,), bool))
        in_sh = (NamedSharding(mesh, P(axis, None)),
                 NamedSharding(mesh, P()), NamedSharding(mesh, P()),
                 NamedSharding(mesh, P()), NamedSharding(mesh, P()))
        out_sh = NamedSharding(mesh, P())
        return step, specs, in_sh, out_sh

    # sharded / quantized: each data-replica fetches ITS OWN doorbell
    # batch and serves ITS OWN pairs — wire bytes / data-degree
    dp = 1
    for a in batch_axes:
        dp *= int(mesh.shape[a])
    bspec = P(batch_axes, None) if batch_axes else P()

    def step(vec_buf, block_ids, queries, pair_slot, pair_valid):
        qspec = (P(axis, None), P(batch_axes, None), P(batch_axes, None),
                 P(batch_axes, None), P(batch_axes, None))

        def span_dma_gather(buf, starts):
            """M_FETCH contiguous span DMAs (the layout's payoff: one
            READ per partition+overflow), psum-assembled."""
            lo = lax.axis_index(axis) * per_shard
            outs = []
            for m in range(M_FETCH):
                s = starts[m]
                mine = (s >= lo) & (s < lo + per_shard)
                sl = jnp.clip(s - lo, 0, per_shard - FETCH_BLOCKS)
                rows = lax.dynamic_slice(buf, (sl, 0), (FETCH_BLOCKS, VBLK))
                outs.append(jnp.where(mine, rows, jnp.zeros((), buf.dtype)))
            spans = jnp.stack(outs)        # (M_FETCH, FETCH_BLOCKS, VBLK)
            return lax.psum(spans, axis)

        def shard_body(buf, ids, q, slot, valid):
            scale = jnp.float32(1.0 / 127.0)
            if variant in ("span_dma", "bf16_serve"):
                starts = ids.reshape(M_FETCH, FETCH_BLOCKS)[:, 0]
                rows8 = span_dma_gather(buf, starts)
                sdt = jnp.bfloat16 if variant == "bf16_serve" else jnp.float32
                rows = rows8.astype(sdt) * scale.astype(sdt)
                rows = rows.reshape(M_FETCH, -1)[slot[0]]
                d, i = serve(rows, q[0], valid[0], dtype=sdt)
                return d[None], i[None]
            ids = ids.reshape(-1)
            if variant == "quantized":
                q8 = jnp.clip(jnp.round(buf / scale), -127, 127
                              ).astype(jnp.int8)
                rows8 = local_gather(q8, ids, jnp.zeros((), jnp.int8))
                rows = rows8.astype(jnp.float32) * scale
            elif variant == "int8_rest":
                rows8 = local_gather(buf, ids, jnp.zeros((), jnp.int8))
                rows = rows8.astype(jnp.float32) * scale
            else:
                rows = local_gather(buf, ids, jnp.zeros((), jnp.float32))
            rows = rows.reshape(M_FETCH, -1)[slot[0]]
            d, i = serve(rows, q[0], valid[0])
            return d[None], i[None]

        return jax.shard_map(
            shard_body, mesh=mesh, in_specs=qspec,
            out_specs=(bspec, bspec), check_vma=False)(
                vec_buf, block_ids, queries, pair_slot, pair_valid)

    vec_dtype = (jnp.int8 if variant in ("int8_rest", "span_dma", "bf16_serve")
                 else jnp.float32)
    specs = dict(
        vec=jax.ShapeDtypeStruct((n_blocks, VBLK), vec_dtype),
        ids=jax.ShapeDtypeStruct((dp, M_FETCH * FETCH_BLOCKS), jnp.int32),
        q=jax.ShapeDtypeStruct((dp, PAIRS, DIM), jnp.float32),
        slot=jax.ShapeDtypeStruct((dp, PAIRS), jnp.int32),
        valid=jax.ShapeDtypeStruct((dp, PAIRS), bool))
    in_sh = (NamedSharding(mesh, P(axis, None)),
             NamedSharding(mesh, bspec),
             NamedSharding(mesh, bspec),
             NamedSharding(mesh, bspec),
             NamedSharding(mesh, bspec))
    out_sh = (NamedSharding(mesh, bspec), NamedSharding(mesh, bspec))
    return step, specs, in_sh, out_sh


def run(variant: str, multi_pod: bool) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    step, specs, in_sh, out_sh = make_step(mesh, variant)
    with mesh:
        lowered = jax.jit(step, in_shardings=in_sh,
                          out_shardings=out_sh).lower(
            specs["vec"], specs["ids"], specs["q"], specs["slot"],
            specs["valid"])
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    coll = parse_collectives(compiled.as_text())
    ma = compiled.memory_analysis()
    res = {
        "cell": f"dhnsw-serve/{variant}",
        "mesh": "multi" if multi_pod else "single",
        "n_devices": mesh.size,
        "flops_dev": float(ca.get("flops", 0.0)),
        "bytes_dev": float(ca.get("bytes accessed", 0.0)),
        "wire_dev": float(coll["wire_bytes_per_device"]),
        "coll_kinds": coll["operand_bytes_by_kind"],
        "n_collectives": coll["n_collectives"],
        "arg_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
    }
    res["t_compute"] = res["flops_dev"] / 197e12
    res["t_memory"] = res["bytes_dev"] / 819e9
    res["t_collective"] = res["wire_dev"] / 50e9
    terms = {k: res[f"t_{k}"] for k in ("compute", "memory", "collective")}
    res["dominant"] = max(terms, key=terms.get)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="all",
                    choices=["baseline", "sharded", "quantized",
                             "int8_rest", "span_dma", "bf16_serve", "all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    variants = (["baseline", "sharded", "quantized", "int8_rest",
                 "span_dma", "bf16_serve"]
                if args.variant == "all" else [args.variant])
    for v in variants:
        res = run(v, args.multi_pod)
        line = json.dumps(res)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
