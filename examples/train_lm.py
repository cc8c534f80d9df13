"""End-to-end training driver: ~100M-param LM for a few hundred steps.

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--arch ...]

Builds a ~100M-parameter variant of an assigned architecture, streams
synthetic token batches, runs the full train loop (AdamW + cosine +
clipping, remat, atomic checkpoints, restart-safe), and prints losses.
"""
import argparse

from repro.configs.base import InputShape
from repro.configs.registry import ARCH_IDS, get_config
from repro.data.synthetic import token_stream
from repro.train.trainer import fit


def hundred_m_config(arch: str):
    """Scale the assigned config down to ~100M params (CPU-trainable)."""
    cfg = get_config(arch)
    kw = dict(n_layers=8, d_model=512, vocab_size=32_000)
    if cfg.n_heads:
        kw.update(n_heads=8, n_kv_heads=min(cfg.n_kv_heads, 4) or 4,
                  head_dim=64)
    if cfg.d_ff:
        kw.update(d_ff=2048)
    if cfg.family == "moe":
        kw.update(n_experts=8, moe_top_k=min(cfg.moe_top_k, 2),
                  expert_d_ff=512)
    if cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_state=64, ssm_head_dim=32)
    if cfg.family == "hybrid":
        kw.update(attn_every=4)
    if cfg.family == "encdec":
        kw.update(n_enc_layers=4, enc_seq=64)
    if cfg.family == "vlm":
        kw.update(n_patches=16)
    return cfg.replace(**kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    cfg = hundred_m_config(args.arch)
    from repro.models.model import param_defs
    from repro.models.params import count_params
    n = count_params(param_defs(cfg))
    print(f"arch {args.arch}: ~{n/1e6:.0f}M params, "
          f"{args.steps} steps @ batch {args.batch} x seq {args.seq}")

    shape = InputShape("example", args.seq, args.batch, "train")
    report = fit(cfg, shape,
                 token_stream(cfg.vocab_size, args.batch, args.seq, seed=0),
                 args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=50,
                 log_every=10)
    print(f"loss: first10={sum(report.losses[:10])/10:.3f} "
          f"last10={sum(report.losses[-10:])/10:.3f}")
    print(f"mean step time: "
          f"{sum(report.step_times[5:]) / max(len(report.step_times) - 5, 1) * 1e3:.0f} ms")
    print(f"checkpoints in {args.ckpt_dir} (restart-safe: rerun resumes)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
