"""Mixture-of-Experts FFN with two execution paths.

* ``_moe_shardmap`` — production path (mesh present, many tokens):
  activations are replicated across the ``model`` axis (Megatron-style),
  experts are sharded over ``model`` (expert parallel) and their ff dim is
  FSDP-sharded over ``data`` (gathered just-in-time).  Each expert owner
  selects its tokens *locally* (tokens are replicated across the EP axis,
  so no dispatch all-to-all is needed), runs the expert matmuls at full
  MXU efficiency, and the combined output is ``psum``-reduced over
  ``model`` — the same collective the TP FFN already pays.

* ``_moe_dense`` — small-token path (decode, smoke tests, meshless):
  classic capacity-based one-hot dispatch einsum.

Both paths use top-k routing with softmax-renormalised gates and
capacity-factor token dropping; both return ``(y, aux_loss)`` where aux
is the standard load-balance loss (Switch/GShard form).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.params import ParamDef


def moe_param_defs(cfg, Lx, st):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    return {
        "router": ParamDef(Lx + (d, E), st + (None, None)),
        "we_g": ParamDef(Lx + (E, d, f), st + ("tp", None, "fsdp")),
        "we_u": ParamDef(Lx + (E, d, f), st + ("tp", None, "fsdp")),
        "we_d": ParamDef(Lx + (E, f, d), st + ("tp", "fsdp", None)),
    }


def _route(cfg, xf, router):
    """xf: (T, d) -> (top_p, top_i) each (T, k) and aux load-balance loss."""
    logits = (xf.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, cfg.moe_top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balance aux: E * sum_e mean(frac_e) * mean(prob_e)
    E = cfg.n_experts
    counts = jnp.zeros(E).at[top_i.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    mean_p = probs.mean(0)
    aux = E * jnp.sum(frac * mean_p)
    return top_p, top_i, aux


def _capacity(cfg, n_tokens: int, ep: int = 1) -> int:
    c = int(math.ceil(n_tokens * cfg.moe_top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(c, 4)


def _expert_mm(buf, wg, wu, wd, dt):
    """buf: (E?, C, d); weights (E?, d, f)/(E?, f, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd).astype(dt)


# ------------------------------------------------------------- dense path

def _moe_dense(cfg, p, x):
    dt = x.dtype
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    top_p, top_i, aux = _route(cfg, xf, p["router"])
    k, E = cfg.moe_top_k, cfg.n_experts
    C = _capacity(cfg, T)
    fe = top_i.reshape(-1)  # (T*k,)
    fp = top_p.reshape(-1)
    ft = jnp.repeat(jnp.arange(T), k)
    # rank of each assignment within its expert (stable, order-of-arrival)
    oh = jax.nn.one_hot(fe, E, dtype=jnp.int32)  # (T*k, E)
    rank = (jnp.cumsum(oh, axis=0) - 1)[jnp.arange(T * k), fe]
    keep = rank < C
    slot = jnp.where(keep, fe * C + rank, E * C)  # E*C = dump row
    buf = jnp.zeros((E * C + 1, d), dt).at[slot].add(
        xf[ft] * keep[:, None].astype(dt))
    buf = buf[:-1].reshape(E, C, d)
    out = _expert_mm(buf, p["we_g"].astype(dt), p["we_u"].astype(dt),
                     p["we_d"].astype(dt), dt)
    flat = jnp.concatenate([out.reshape(E * C, d),
                            jnp.zeros((1, d), dt)], axis=0)
    contrib = flat[slot] * (fp[:, None] * keep[:, None]).astype(dt)
    y = jnp.zeros((T, d), dt).at[ft].add(contrib)
    return y.reshape(B, S, d), aux


# --------------------------------------------------------- shard_map path

def _moe_shardmap(cfg, p, x, mesh):
    dt = x.dtype
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    ep_ax = "model"
    fsdp_ax = "data" if "data" in names else None
    ep = mesh.shape[ep_ax]
    E = cfg.n_experts
    assert E % ep == 0, f"experts {E} not divisible by EP size {ep}"
    E_loc = E // ep
    f = cfg.expert_d_ff
    fsdp = mesh.shape[fsdp_ax] if fsdp_ax else 1
    shard_f = fsdp_ax is not None and f % fsdp == 0

    def inner(x_loc, router, wg, wu, wd):
        B_loc, S, d = x_loc.shape
        xf = x_loc.reshape(-1, d)
        T = xf.shape[0]
        top_p, top_i, aux = _route(cfg, xf, router)
        k = cfg.moe_top_k
        C = _capacity(cfg, T, ep)
        my = lax.axis_index(ep_ax)
        fe = top_i.reshape(-1)
        fp = top_p.reshape(-1)
        ft = jnp.repeat(jnp.arange(T), k)
        order = jnp.argsort(fe, stable=True)
        se, sp, stk = fe[order], fp[order], ft[order]
        first = jnp.searchsorted(se, se, side="left")
        rank = jnp.arange(T * k) - first
        keep = rank < C
        rel = se - my * E_loc
        mine = (rel >= 0) & (rel < E_loc) & keep
        slot = jnp.where(mine, rel * C + rank, E_loc * C)
        buf = jnp.zeros((E_loc * C + 1, d), dt).at[slot].add(
            xf[stk] * mine[:, None].astype(dt))
        buf = buf[:-1].reshape(E_loc, C, d)
        if shard_f:  # FSDP: gather expert weights just-in-time (bf16 wire)
            wg_g = lax.all_gather(wg.astype(dt), fsdp_ax, axis=2, tiled=True)
            wu_g = lax.all_gather(wu.astype(dt), fsdp_ax, axis=2, tiled=True)
            wd_g = lax.all_gather(wd.astype(dt), fsdp_ax, axis=1, tiled=True)
        else:
            wg_g, wu_g, wd_g = wg.astype(dt), wu.astype(dt), wd.astype(dt)
        out = _expert_mm(buf, wg_g, wu_g, wd_g, dt)
        flat = jnp.concatenate([out.reshape(E_loc * C, d),
                                jnp.zeros((1, d), dt)], axis=0)
        contrib = flat[slot] * (sp[:, None] * mine[:, None]).astype(dt)
        y = jnp.zeros((T, d), dt).at[stk].add(contrib)
        y = lax.psum(y, ep_ax)
        return y.reshape(B_loc, S, d), aux

    wspec_gu = P(ep_ax, None, fsdp_ax if shard_f else None)
    wspec_d = P(ep_ax, fsdp_ax if shard_f else None, None)
    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(batch_axes or None, None, None), P(None, None),
                  wspec_gu, wspec_gu, wspec_d),
        out_specs=(P(batch_axes or None, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["we_g"], p["we_u"], p["we_d"])
    return y, aux


def moe_ffn(cfg, p, x, mesh=None):
    """x: (B, S, d) -> (y, aux_loss)."""
    use_shardmap = (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and cfg.n_experts % mesh.shape["model"] == 0
    )
    if use_shardmap:
        return _moe_shardmap(cfg, p, x, mesh)
    return _moe_dense(cfg, p, x)
