"""Fixed-shape JAX HNSW search (greedy descent + ef beam at layer 0).

The paper's greedy walk has data-dependent control flow; on TPU we need
static shapes, so: adjacency is dense ``(L, N, deg)`` with -1 padding,
the visited set is an explicit ``(N,)`` bitmap, and the beam is a sorted
``(ef,)`` array updated with masked merges inside ``lax.while_loop``.
Semantics match host HNSW exactly (same stop rule: terminate when the
closest unexpanded candidate is farther than the worst of the ef set).

Two query paths over a *loaded* partition:
  * ``beam_search``      — the faithful graph walk (paper's algorithm);
  * ``scan_partition``   — beyond-paper TPU mode: brute-force the whole
    fetched partition through the MXU distance+top-k kernel.  On TPU the
    partition is already resident after the fetch, and a 2k-vector tiled
    matmul beats a pointer-chasing walk; the graph is still what decides
    WHICH partitions to fetch (the paper's actual bandwidth win).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

INF = jnp.inf


def _sq_dists(vectors, ids, q):
    """Squared L2 from q (D,) to vectors[ids]; invalid ids (<0) -> inf."""
    valid = ids >= 0
    rows = vectors[jnp.where(valid, ids, 0)]
    d = jnp.sum(jnp.square(rows - q[None, :]), axis=-1)
    return jnp.where(valid, d, INF)


def greedy_descent(vectors, adjacency, q, entry, n_levels: int,
                   max_hops: int = 64):
    """Layers top..1: hill-climb to the locally-closest node per layer."""
    d_entry = jnp.sum(jnp.square(vectors[entry] - q))

    def one_layer(carry, l_rev):
        u, du = carry
        layer = n_levels - 1 - l_rev  # top .. 1

        def cond(s):
            _, _, moved, hops = s
            return moved & (hops < max_hops)

        def body(s):
            u, du, _, hops = s
            nbrs = adjacency[layer, u]
            d = _sq_dists(vectors, nbrs, q)
            j = jnp.argmin(d)
            better = d[j] < du
            return (jnp.where(better, nbrs[j], u),
                    jnp.where(better, d[j], du), better, hops + 1)

        u, du, _, _ = lax.while_loop(cond, body, (u, du, True, 0))
        return (u, du), None

    if n_levels <= 1:
        return entry, d_entry
    (u, du), _ = lax.scan(one_layer, (entry, d_entry),
                          jnp.arange(n_levels - 1))
    return u, du


def beam_search(vectors, adjacency, q, entry, *, ef: int,
                n_levels: int = 1, max_iters: Optional[int] = None,
                visited_size: Optional[int] = None):
    """Full HNSW query for one vector.

    Returns (dists (ef,), ids (ef,)) sorted ascending; -1/inf padding.
    ``adjacency``: (L, N, deg) i32.  vmap over q/entry for batches.
    """
    n = vectors.shape[0] if visited_size is None else visited_size
    max_iters = max_iters or (2 * ef + 8)
    deg = adjacency.shape[2]

    ep, dep = greedy_descent(vectors, adjacency, q, entry, n_levels)

    beam_d = jnp.full((ef,), INF).at[0].set(dep)
    beam_i = jnp.full((ef,), -1, jnp.int32).at[0].set(ep)
    expanded = jnp.zeros((ef,), bool)
    visited = jnp.zeros((n,), bool).at[ep].set(True)

    def cond(state):
        beam_d, beam_i, expanded, visited, it = state
        cand = jnp.where(~expanded & (beam_i >= 0), beam_d, INF)
        best_un = jnp.min(cand)
        worst = jnp.max(jnp.where(beam_i >= 0, beam_d, -INF))
        return (it < max_iters) & jnp.isfinite(best_un) & (best_un <= worst)

    def body(state):
        beam_d, beam_i, expanded, visited, it = state
        cand = jnp.where(~expanded & (beam_i >= 0), beam_d, INF)
        pos = jnp.argmin(cand)
        u = beam_i[pos]
        expanded = expanded.at[pos].set(True)

        nbrs = adjacency[0, u]                      # (deg,)
        fresh = (nbrs >= 0) & ~visited[jnp.where(nbrs >= 0, nbrs, 0)]
        visited = visited.at[jnp.where(fresh, nbrs, 0)].set(True)
        nd = jnp.where(fresh, _sq_dists(vectors, nbrs, q), INF)

        all_d = jnp.concatenate([beam_d, nd])
        all_i = jnp.concatenate([beam_i, jnp.where(fresh, nbrs, -1)])
        all_e = jnp.concatenate([expanded, jnp.zeros((deg,), bool)])
        order = jnp.argsort(all_d)[:ef]
        return (all_d[order], all_i[order], all_e[order], visited, it + 1)

    beam_d, beam_i, expanded, visited, _ = lax.while_loop(
        cond, body, (beam_d, beam_i, expanded, visited, 0))
    return beam_d, beam_i


def batched_beam_search(vectors, adjacency, queries, entry, *, ef: int,
                        n_levels: int = 1, max_iters: Optional[int] = None):
    """vmap wrapper: queries (B, D) -> (B, ef) dists/ids."""
    fn = functools.partial(beam_search, vectors, adjacency, ef=ef,
                           n_levels=n_levels, max_iters=max_iters)
    return jax.vmap(lambda q: fn(q, entry))(queries)


# ------------------------------------------------------------- meta routing

@functools.partial(jax.jit, static_argnames=("b", "ef", "n_levels"))
def meta_route(meta_vectors, meta_adjacency, queries, entry, *, b: int,
               ef: int = 0, n_levels: int = 3):
    """Route a batch of queries through the cached meta-HNSW.

    Returns (B, b) partition ids (= L0 rep indices), nearest-first.  This
    is the only index the compute pool holds; everything else is fetched.
    """
    ef = max(ef, 2 * b, 8)
    with jax.named_scope("route/meta_walk"):
        d, i = batched_beam_search(meta_vectors, meta_adjacency, queries,
                                   entry, ef=ef, n_levels=n_levels)
        return i[:, :b], d[:, :b]


# ------------------------------------------------------------- scan mode

def scan_partition(part_vectors, q, k: int, n_valid=None):
    """Exact top-k within one loaded partition ((Np, D) padded).

    ``n_valid`` masks layout padding / unused overflow slots.  Pure-jnp
    path.
    """
    d = jnp.sum(jnp.square(part_vectors - q[None, :]), axis=-1)
    if n_valid is not None:
        d = jnp.where(jnp.arange(d.shape[0]) < n_valid, d, INF)
    nd, ni = lax.top_k(-d, k)
    return -nd, ni


def merge_topk(d_a, i_a, d_b, i_b, k: int):
    """Merge two sorted top-k lists (per-query running results across
    partition rounds).  Ids are globally unique (partitions are disjoint),
    so plain merge-sort-take-k."""
    d = jnp.concatenate([d_a, d_b], axis=-1)
    i = jnp.concatenate([i_a, i_b], axis=-1)
    order = jnp.argsort(d, axis=-1)[..., :k]
    return (jnp.take_along_axis(d, order, axis=-1),
            jnp.take_along_axis(i, order, axis=-1))
