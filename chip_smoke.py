#!/usr/bin/env python3
"""One-chip smoke test of the served d-HNSW path at SIFT1M shape.

    python chip_smoke.py                  # on a TPU host: 950k x 128-d, L2
    python chip_smoke.py --rehearse       # on the CPU, tiny n, kernels interpreted

Generates a SIFT1M-shaped dataset from a seed (``sift_like``: 950,000
x 128 f32 with exact top-100 ground truth; ``CHIP_ROWS`` says why not
1M), builds the index once with the paper's 500 partitions, and serves
it through ``SearchServer`` from several client threads with two
engines:

  (a) the paper path: ``mode="full"``, graph search, exact vectors;
  (b) the int8 tier: scan mode with a dense-resident quantized tier, so
      stage 1 is the fused ``quant_topk`` Pallas kernel (asserted), and
      that kernel is compared with the jnp ``quant_topk_ref`` on the
      same device and inputs.

Each engine must answer every request and reach its recall@10 floor.
Earlier lines report set-up (build) time, sizes and device memory;
the last line is one JSON object naming the device.  Without a TPU, and
without ``--rehearse``, the script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K = 10
QUERIES = 256
CLIENTS = 8                  # concurrent client threads per engine
ROWS_PER_REQUEST = 4
N_REP = 500                  # the paper's partition count on SIFT1M
TARGET_ROWS = 1_000_000      # SIFT1M
# Every partition's span is padded to the largest partition (np_max), and
# this generator's 1000 tight clusters make one partition hold ~15x the
# mean at 1M rows (np_max 31,292): engine (b)'s padded f32 region, int8
# mirror and cache tiers then need ~18 GB of the chip's 16 GB.  At 950k
# rows np_max is 18,668 and both engines fit.
CHIP_ROWS = 950_000
REHEARSE_ROWS = 20_000
# recall@10 floors per row count: the CPU run of this script at the
# same configuration (--rehearse --n <rows>), less 0.02
RECALL_FLOORS = {
    CHIP_ROWS: {"graph": 0.340625 - 0.02, "int8": 1.0 - 0.02},
    REHEARSE_ROWS: {"graph": 0.351171875 - 0.02, "int8": 1.0 - 0.02},
}


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {key: stats.get(key, "not reported")
            for key in ("bytes_in_use", "peak_bytes_in_use")}


def _serve(engine, queries):
    """Drive ``engine`` through a ``SearchServer`` from ``CLIENTS``
    threads; returns (gids (Q, K), per-request stats, answered count)."""
    from repro.serve.batcher import BatchPolicy
    from repro.serve.server import SearchServer

    chunks = [(s, queries[s:s + ROWS_PER_REQUEST])
              for s in range(0, len(queries), ROWS_PER_REQUEST)]
    futures = [None] * len(chunks)
    with SearchServer(engine, BatchPolicy(max_batch=64,
                                          max_wait_s=5e-3)) as srv:
        def client(c: int):
            for j in range(c, len(chunks), CLIENTS):
                futures[j] = srv.search_async(chunks[j][1], K)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        gids = np.full((len(queries), K), -1, np.int64)
        stats = []
        for (s, chunk), fut in zip(chunks, futures):
            _, g, st = fut.result(timeout=900)   # re-raises a failed window
            gids[s:s + len(chunk)] = g
            stats.append(st)
    return gids, stats, len(chunks)


def _check_stage1(client, queries, group: int) -> dict:
    """The Pallas stage 1 against ``quant_topk_ref`` on the same device
    and inputs: id agreement, and each one's distances against float64
    distances to the same dequantized rows computed on the host."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.quant_topk.ops import quant_topk

    m = max(int(client.cfg.rerank_m) or 2 * K, K)
    q = jnp.asarray(queries)
    args = (q, client._flat_codes, client._flat_scales, m, group)
    d_k, i_k, _ = jax.block_until_ready(
        quant_topk(*args, n_valid=client._flat_n))
    d_r, i_r, _ = jax.block_until_ready(
        quant_topk(*args, n_valid=client._flat_n, use_ref=True))
    d_k, i_k, d_r, i_r = map(np.asarray, (d_k, i_k, d_r, i_r))
    if (i_k < 0).any() or (i_k >= client._flat_n).any():
        _fail("stage 1 kernel returned ids outside the live rows")
    overlap = float(np.mean([len(set(a) & set(b)) / m
                             for a, b in zip(i_k.tolist(), i_r.tolist())]))
    codes = np.asarray(client._flat_codes)
    scales = np.asarray(client._flat_scales)

    def exact(ids):
        x = (codes[ids].astype(np.float64)
             * np.repeat(scales[ids], group, axis=-1))
        return ((x - np.asarray(queries, np.float64)[:, None, :]) ** 2).sum(-1)

    ex_k, ex_r = exact(i_k), exact(i_r)
    scale = float(np.abs(ex_r).max())
    return {"overlap": overlap,
            "kernel_max_abs_err": float(np.abs(d_k - ex_k).max()),
            "ref_max_abs_err": float(np.abs(d_r - ex_r).max()),
            "kth_dist_gap": float(np.abs(ex_k[:, -1] - ex_r[:, -1]).max()),
            "dist_scale": scale}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size with Pallas "
                         "kernels interpreted (never counts as a chip run)")
    ap.add_argument("--n", type=int, default=0,
                    help=f"rows (default {CHIP_ROWS:,}; "
                         f"{REHEARSE_ROWS:,} with --rehearse)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    n = args.n or (REHEARSE_ROWS if args.rehearse else CHIP_ROWS)

    import jax
    dev = jax.devices()[0]
    want = "cpu" if args.rehearse else "tpu"
    if dev.platform != want:
        _fail(f"JAX found platform {dev.platform!r}, need {want!r}")
    # before the first line of output: without the repo, print nothing
    from repro.core import DHNSWEngine, EngineConfig, recall_at_k
    from repro.data.synthetic import sift_like
    from repro.launch.compile_cache import enable_compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}", flush=True)
    print(f"compile_cache: {enable_compile_cache()}", flush=True)
    floors = RECALL_FLOORS.get(n)
    if floors is None:
        _fail(f"no recall floors recorded for n={n}; "
              f"known sizes: {sorted(RECALL_FLOORS)}")

    # ---- set-up: data + one build (not part of any serving number)
    t0 = time.perf_counter()
    ds = sift_like(n=n, n_queries=QUERIES, seed=args.seed)
    gt = ds.gt_ids[:, :K]
    print(f"setup.data: rows={n} dim={ds.data.shape[1]} "
          f"queries={len(ds.queries)} seconds={time.perf_counter() - t0}",
          flush=True)
    if n < TARGET_ROWS:
        print(f"setup.cut: rows {n} of the {TARGET_ROWS} SIFT1M target",
              flush=True)

    cfg_a = EngineConfig(mode="full", search_mode="graph", quant="none",
                         n_rep=N_REP, seed=args.seed)
    t0 = time.perf_counter()
    eng = DHNSWEngine(cfg_a).build(ds.data)
    spec = eng.store.spec
    print(f"setup.build: seconds={time.perf_counter() - t0} "
          f"partitions={spec.n_partitions} np_max={spec.np_max} "
          f"blocks={spec.n_blocks} "
          f"host_region_bytes={eng.store.total_bytes()} "
          f"staged_device_bytes={eng.pool.staging['device_bytes']}",
          flush=True)

    results = {}

    # ---- (a) the paper path: full d-HNSW, graph search, exact vectors
    gids, stats, answered = _serve(eng, ds.queries)
    results["graph"] = recall_at_k(gids, gt)
    print(f"engine_a: mode=full search=graph quant=none "
          f"requests_answered={answered} recall@10={results['graph']} "
          f"floor={floors['graph']} memory={json.dumps(_memory(dev))}",
          flush=True)

    # ---- (b) the int8 tier on the same build, dense-resident stage 1
    meta, store = eng.meta, eng.store
    del eng
    gc.collect()
    cfg_b = EngineConfig(mode="full", search_mode="scan", quant="int8",
                         quant_kernel="auto", cache_frac=0.4, n_rep=N_REP,
                         seed=args.seed)
    eng = DHNSWEngine(cfg_b)
    eng.client.adopt_built(meta, store, ds.data)
    if not eng.client._flat_kernel_active():
        _fail("int8 tier is not dense-resident: stage 1 would not use "
              f"quant_topk (quant capacity {eng.tiers.quant.capacity} "
              f"< {spec.n_partitions} partitions)")
    print(f"engine_b.setup: quant_capacity={eng.tiers.quant.capacity} "
          f"staged_device_bytes={eng.pool.staging['device_bytes']} "
          f"memory={json.dumps(_memory(dev))}", flush=True)
    gids, stats, answered = _serve(eng, ds.queries)
    results["int8"] = recall_at_k(gids, gt)
    impl = {st.get("stage1_impl") for st in stats}
    want_impl = "ref" if args.rehearse else "pallas"
    print(f"engine_b: mode=full search=scan quant=int8 stage1_impl="
          f"{','.join(sorted(map(str, impl)))} requests_answered={answered} "
          f"recall@10={results['int8']} floor={floors['int8']} "
          f"memory={json.dumps(_memory(dev))}", flush=True)
    if impl != {want_impl}:
        _fail(f"stage1_impl {impl}, expected {{{want_impl!r}}}")

    cmp = _check_stage1(eng.client, ds.queries, cfg_b.quant_group)
    print(f"engine_b.stage1_vs_ref: {json.dumps(cmp)}", flush=True)
    # the kernel may round differently from the jnp ref on this device,
    # but must find the same candidates and be no less exact than it
    if cmp["overlap"] < 0.9:
        _fail(f"stage 1 kernel and ref share {cmp['overlap']} of ids")
    if cmp["kernel_max_abs_err"] > max(2 * cmp["ref_max_abs_err"],
                                       1e-3 * cmp["dist_scale"]):
        _fail("stage 1 kernel distances are further from float64 than "
              "the ref's")

    for name, floor in floors.items():
        if results[name] < floor:
            _fail(f"engine {name} recall@10 {results[name]} < floor {floor}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
