"""Quantized-tier sweep: recall vs bytes-on-the-wire across tier splits.

For each scheme the staged int8 path is compared against the exact
single-tier engine at the SAME cache byte budget:

  * ``quant=none``  — every miss moves a full-precision span;
  * ``quant=int8``  — stage-1 misses move int8 codes + codebook blocks
                      into a ~3-4x larger quantized tier, stage 2 moves
                      only the candidate rows it re-ranks.

The sweep axes are the tier split (``exact_frac`` — the share of the
byte budget kept as full-precision slots) and the re-rank pool size
(``rerank_m``).  Each cell runs several query batches (so tier reuse,
not just the cold fetch, is measured) and reports recall@10 against the
dataset's exact ground truth next to total fetched/saved bytes.

Also A/Bs the fused int8 Pallas kernel (kernels/quant_topk) against its
pure-jnp oracle on a flat database — match + wall time.

Writes ``BENCH_quant.json``.  ``--smoke`` is the CI crash check: tiny
config, asserts nothing about perf.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import DHNSWEngine, EngineConfig, recall_at_k
from repro.core.cost_model import RDMA_100G
from repro.data.synthetic import sift_like


def run_cell(data, queries, gt, *, quant: str, exact_frac: float,
             rerank_m: int, n_rep: int, n_batches: int, k: int = 10,
             quant_kernel: str = "off", cache_frac: float = 0.25,
             seed: int = 0) -> dict:
    cfg = EngineConfig(mode="full", search_mode="scan", b=6, ef=48,
                       n_rep=n_rep, cache_frac=cache_frac, doorbell=16,
                       fabric=RDMA_100G, seed=seed, quant=quant,
                       exact_frac=exact_frac, rerank_m=rerank_m,
                       quant_kernel=quant_kernel)
    eng = DHNSWEngine(cfg).build(data)
    per = max(len(queries) // n_batches, 1)
    tot_bytes = tot_saved = trips = 0.0
    recs = []
    t0 = time.perf_counter()
    for i in range(n_batches):
        qb = queries[i * per:(i + 1) * per]
        _, g, st = eng.search(qb, k=k)
        tot_bytes += st["net"]["bytes"]
        tot_saved += st["net"]["bytes_saved"]
        trips += st["net"]["round_trips"]
        recs.append(recall_at_k(g, gt[i * per:(i + 1) * per, :k]))
    wall = time.perf_counter() - t0
    row = {"quant": quant, "recall": round(float(np.mean(recs)), 4),
           "mbytes": round(tot_bytes / 1e6, 3),
           "mbytes_saved": round(tot_saved / 1e6, 3),
           "round_trips": trips, "wall_s": round(wall, 2)}
    if quant != "none":
        row.update(exact_frac=exact_frac, rerank_m=rerank_m,
                   quant_slots=eng.tiers.quant.capacity,
                   exact_slots=eng.tiers.exact.capacity)
    if quant_kernel != "off":
        row.update(quant_kernel=quant_kernel,
                   kernel_active=st.get("quant_kernel") == "flat")
    return row


def kernel_ab(n: int = 4096, d: int = 128, k: int = 10,
              seed: int = 0) -> dict:
    """Fused int8 Pallas kernel vs the pure-jnp oracle on a flat DB."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.quant_topk.ops import quant_topk
    from repro.quant.codec import quantize_groups

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((64, d)).astype(np.float32)
    codes, scales = quantize_groups(x, 32)
    qj, cj, sj = jnp.asarray(q), jnp.asarray(codes), jnp.asarray(scales)

    out = {}
    for name, use_ref in (("pallas", False), ("ref", True)):
        dd, ii, _ = quant_topk(qj, cj, sj, k, 32, use_ref=use_ref)
        jax.block_until_ready((dd, ii))
        t0 = time.perf_counter()
        dd, ii, _ = quant_topk(qj, cj, sj, k, 32, use_ref=use_ref)
        jax.block_until_ready((dd, ii))
        out[f"{name}_us"] = round((time.perf_counter() - t0) * 1e6, 1)
        out[name] = (np.asarray(dd), np.asarray(ii))
    match = float(np.mean(out["pallas"][1] == out["ref"][1]))
    return {"bench": "quant_topk_kernel", "n": n, "d": d, "k": k,
            "id_match": match, "pallas_us": out["pallas_us"],
            "ref_us": out["ref_us"]}


def run(*, smoke: bool = False, out: str = "BENCH_quant.json",
        seed: int = 0) -> dict:
    if smoke:
        n, n_rep, n_batches = 1500, 12, 2
        splits, pools = (0.25,), (0,)
        kab = kernel_ab(n=512, d=64, k=5, seed=seed)
    else:
        n, n_rep, n_batches = 20_000, 64, 4
        splits, pools = (0.0, 0.25, 0.5), (0, 20, 40)
        kab = kernel_ab(seed=seed)
    ds = sift_like(n=n, n_queries=256, seed=seed)

    rows = [run_cell(ds.data, ds.queries, ds.gt_ids, quant="none",
                     exact_frac=0.25, rerank_m=0, n_rep=n_rep,
                     n_batches=n_batches, seed=seed)]
    base = rows[0]["mbytes"]
    print(f"{'quant':6s} {'split':>5s} {'m':>4s} {'recall':>7s} "
          f"{'MB':>9s} {'saved MB':>9s} {'reduction':>9s}")
    print(f"{'none':6s} {'-':>5s} {'-':>4s} {rows[0]['recall']:7.4f} "
          f"{base:9.2f} {'-':>9s} {'-':>9s}", flush=True)
    for split in splits:
        for m in pools:
            row = run_cell(ds.data, ds.queries, ds.gt_ids, quant="int8",
                           exact_frac=split, rerank_m=m, n_rep=n_rep,
                           n_batches=n_batches, seed=seed)
            row["bytes_reduction"] = round(base / max(row["mbytes"], 1e-9), 2)
            rows.append(row)
            print(f"{'int8':6s} {split:5.2f} {m:4d} {row['recall']:7.4f} "
                  f"{row['mbytes']:9.2f} {row['mbytes_saved']:9.2f} "
                  f"x{row['bytes_reduction']:8.2f}", flush=True)

    # dense-resident flat stage-1 A/B: the quant_topk Pallas kernel over
    # the whole resident int8 database vs the per-pair jnp staged path
    # (cache budget raised so the quant tier holds every partition)
    for qk in ("auto", "ref"):
        row = run_cell(ds.data, ds.queries, ds.gt_ids, quant="int8",
                       exact_frac=0.25, rerank_m=0, n_rep=n_rep,
                       n_batches=n_batches, quant_kernel=qk,
                       cache_frac=0.6, seed=seed)
        row["bytes_reduction"] = round(base / max(row["mbytes"], 1e-9), 2)
        rows.append(row)
        tag = {"auto": "flatk", "ref": "flatr"}[qk]
        print(f"{tag:6s} {0.25:5.2f} {0:4d} {row['recall']:7.4f} "
              f"{row['mbytes']:9.2f} {row['mbytes_saved']:9.2f} "
              f"x{row['bytes_reduction']:8.2f}  "
              f"active={row['kernel_active']}", flush=True)

    print(f"kernel A/B: id_match {kab['id_match']:.3f}  "
          f"pallas {kab['pallas_us']} us vs ref {kab['ref_us']} us")
    blob = {"bench": "quant", "smoke": smoke, "n": n, "n_rep": n_rep,
            "n_batches": n_batches, "rows": rows, "kernel": kab}
    with open(out, "w") as f:
        json.dump(blob, f, indent=2)
    print(f"wrote {out} ({len(rows)} rows)")
    return blob


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config; crash-check only")
    ap.add_argument("--out", default="BENCH_quant.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out, seed=args.seed)


if __name__ == "__main__":
    main()
