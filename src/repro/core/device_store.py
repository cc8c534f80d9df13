"""Device-side store: fetched-span decode + per-partition search.

Everything here is static-shaped and jit-friendly.  A fetch span is
``(fetch_blocks, gblk)`` int32 + ``(fetch_blocks, vblk)`` float32 — the
unit one doorbell descriptor covers — or, in the int8 tier, the same
graph blocks + int8 codes + their codebook scales.  ``decode_span`` turns
a span of either tier + its metadata row into padded search arrays; the
two search paths (faithful graph walk / MXU scan) run on the decoded
view, and ``serve_and_merge`` serves one round of either tier.

The jitted bodies name their steps with ``jax.named_scope`` as
``<layer>/<step>`` (``serve/decode``, ``serve/walk``, ``serve/merge``,
``fetch/write_slots``, ``stage1/payload``, ``rerank/gather_rows``,
``rerank/exact``): the names their operations carry in a profiler
trace.  Scopes change only the operations' metadata, not the compiled
program.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import search as S
from repro.core.layout import (LayoutSpec, MT_BLK_START, MT_ENTRY,
                               MT_N_BASE, MT_OV_A, MT_OV_B, MT_SIDE)


class DecodedPartition(NamedTuple):
    vectors: jax.Array    # (np_max + ov_cap, D) — base then overflow slots
    adjacency: jax.Array  # (1, np_max, deg) local ids, -1 pad
    gids: jax.Array       # (np_max + ov_cap,) global ids, -1 pad
    valid: jax.Array      # (np_max + ov_cap,) bool — base n + live overflow
    entry: jax.Array      # () local entry id (the representative)


def decode_span(spec: LayoutSpec, g_span, v_span, meta_row, qs_span=None):
    """g_span (fetch_blocks, gblk) i32; v_span (fetch_blocks, vblk) f32,
    or int8 codes when ``qs_span`` (fetch_blocks, n_qgroups) f32 holds
    their codebook scales — the quantized tier's span.

    Returns (DecodedPartition with f32 vectors, rows (np_max + ov_cap,)
    i32): ``rows`` are the slots' exact-row addresses into
    ``vec_buf.reshape(-1, dim)``, what the int8 tier's re-rank reads.
    """
    side = meta_row[MT_SIDE]
    n_base = meta_row[MT_N_BASE]
    gflat = g_span.reshape(-1)
    vflat = v_span.reshape(-1)
    base_blk = side * spec.ov_blocks         # first block of the base data
    ov_blk = (1 - side) * spec.data_blocks   # first block of the overflow

    data_g = lax.dynamic_slice(gflat, (base_blk * spec.gblk,),
                               (spec.np_max * (spec.deg + 1),))
    adjacency = data_g[: spec.np_max * spec.deg].reshape(spec.np_max, spec.deg)
    base_gids = data_g[spec.np_max * spec.deg:]
    ov_gids = lax.dynamic_slice(gflat, (ov_blk * spec.gblk,), (spec.ov_cap,))

    def vectors(blk, n):
        off = blk * spec.vblk
        x = lax.dynamic_slice(vflat, (off,), (n * spec.dim,))
        if qs_span is not None:      # dequantize: one multiply per group
            g = spec.quant_group
            scales = lax.dynamic_slice(qs_span.reshape(-1), (off // g,),
                                       (n * spec.dim // g,))
            x = x.astype(jnp.float32).reshape(-1, g) * scales[:, None]
        return x.reshape(n, spec.dim)

    cnt_a, cnt_b = meta_row[MT_OV_A], meta_row[MT_OV_B]
    ov_idx = jnp.arange(spec.ov_cap)
    # A's inserts fill the front, B's fill the back; a fetch sees both but
    # only its own side's slots belong to this partition
    ov_mine = jnp.where(side == 0, ov_idx < cnt_a,
                        ov_idx >= spec.ov_cap - cnt_b)
    base_valid = jnp.arange(spec.np_max) < n_base

    # vblk = slot_vecs * dim, so row r of the region lives at flat row
    # index block * slot_vecs + local offset
    blk_start = meta_row[MT_BLK_START]
    rows = jnp.concatenate([
        (blk_start + base_blk) * spec.slot_vecs + jnp.arange(spec.np_max),
        (blk_start + ov_blk) * spec.slot_vecs + jnp.arange(spec.ov_cap),
    ]).astype(jnp.int32)
    part = DecodedPartition(
        vectors=jnp.concatenate([vectors(base_blk, spec.np_max),
                                 vectors(ov_blk, spec.ov_cap)], axis=0),
        adjacency=adjacency[None],
        gids=jnp.concatenate([base_gids, ov_gids]),
        valid=jnp.concatenate([base_valid, ov_mine]),
        entry=meta_row[MT_ENTRY],
    )
    return part, rows


def search_decoded_scan(part: DecodedPartition, q, k: int):
    """Exact top-k over every valid vector (base + overflow) — the
    beyond-paper MXU path.  Returns (dists (k,), local ids (k,)), -1
    where the distance is inf (fewer than k valid vectors)."""
    d = jnp.sum(jnp.square(part.vectors - q[None, :]), axis=-1)
    d = jnp.where(part.valid, d, jnp.inf)
    n = d.shape[0]
    nd, ni = lax.top_k(-d, min(k, n))
    if k > n:                        # a candidate pool wider than the span
        nd = jnp.concatenate([nd, jnp.full((k - n,), -jnp.inf, nd.dtype)])
        ni = jnp.concatenate([ni, jnp.full((k - n,), -1, ni.dtype)])
    return -nd, jnp.where(jnp.isfinite(nd), ni, -1)


def search_decoded_graph(part: DecodedPartition, q, k: int, ef: int):
    """Paper-faithful: beam-search the sub-HNSW graph over the base
    vectors, then brute-scan the (tiny) live overflow slice and merge —
    exactly how the paper covers not-yet-relinked inserted vectors.
    Returns (dists (k,), local ids (k,)), -1 where the distance is inf."""
    np_max = part.adjacency.shape[1]
    bd, bi = S.beam_search(part.vectors[:np_max], part.adjacency, q,
                           part.entry, ef=max(ef, k), n_levels=1)
    bd = jnp.where((bi >= 0) & part.valid[jnp.maximum(bi, 0)], bd, jnp.inf)
    ov_vecs = part.vectors[np_max:]
    ov_d = jnp.sum(jnp.square(ov_vecs - q[None, :]), axis=-1)
    ov_d = jnp.where(part.valid[np_max:], ov_d, jnp.inf)
    od, oi = lax.top_k(-ov_d, min(k, ov_vecs.shape[0]))
    d, li = S.merge_topk(bd[:k], bi[:k], -od, np_max + oi, k)
    return d, jnp.where(jnp.isfinite(d), li, -1)


@functools.partial(jax.jit,
                   static_argnames=("spec", "k", "ef", "mode", "n_lanes"),
                   donate_argnums=(4, 5))
def serve_and_merge(spec: LayoutSpec, cache, meta_table, queries, run_d,
                    run_p, pair_qi, pair_pids, pair_slots, pair_ranks,
                    pair_valid, *, k: int, ef: int, mode: str, n_lanes: int):
    """One round, fused: per-pair top-k inside the pair's partition, then a
    single vectorized scatter-merge into the batch's running top-k.

    Replaces the host loop that merged each pair's ``(k,)`` list into its
    query's running list one ``np.argsort`` at a time.  All staging is
    device-side gathers from arrays resident since batch start:

    cache:      the tier's slot arrays — (g, v) exact f32 spans, or
                (g, qv, qs) int8 codes + scales, dequantized on decode
    meta_table: (n_partitions, META_COLS) — the whole cached table; each
                pair gathers its own row (no per-round host rebuild)
    queries:    (B, D) — the full query batch; gathered by ``pair_qi``
    run_d/run_p:(B, k) running top-k carried across rounds (donated);
                ``run_p`` holds gids (B, k), or for the int8 tier the
                payload (B, k, 3) [gid, exact_row, pid] its re-rank needs
    pair_qi:    (n_pairs,) query index; padding lanes point at row B so
                the ``(B+1, n_lanes, k)`` scatter drops them
    pair_ranks: (n_pairs,) merge lane — occurrence index of the pair's
                query within this round (unique per (query, round))
    Returns the updated (run_d, run_p).

    Merge semantics are identical to folding the pairs in order through a
    stable sort (stable argsort over [running | lane 0 | lane 1 | ...] is
    associative with the sequential stable merges the host loop did), so
    results are bit-identical to the old path.
    """
    with jax.named_scope("serve/decode"):
        mrows = meta_table[pair_pids]
        qs = queries[pair_qi]      # padding qi == B clamps; masked below

    def one(slot, mrow, q, ok, pid):
        with jax.named_scope("serve/decode"):
            g_span, v_span, *qs_span = (c[slot] for c in cache)
            part, rows = decode_span(spec, g_span, v_span, mrow, *qs_span)
        with jax.named_scope("serve/walk"):
            if mode == "graph":
                d, li = search_decoded_graph(part, q, k, ef)
            else:
                d, li = search_decoded_scan(part, q, k)
            live = ok & (li >= 0)
            safe = jnp.maximum(li, 0)
            p = jnp.where(live, part.gids[safe], -1)
            if run_p.ndim == 3:      # int8 tier: [gid, exact_row, pid]
                p = jnp.stack([p, jnp.where(live, rows[safe], -1),
                               jnp.where(live, pid, -1)], axis=-1)
            return jnp.where(live, d, jnp.inf), p

    d, p = jax.vmap(one)(pair_slots, mrows, qs, pair_valid, pair_pids)
    with jax.named_scope("serve/merge"):
        return merge_ranked(run_d, run_p, pair_qi, pair_ranks, d, p,
                            n_lanes=n_lanes)


@functools.partial(jax.jit, static_argnames=("n_lanes",))
def merge_ranked(run_d, run_p, pair_qi, pair_ranks, d, p, *, n_lanes: int):
    """Scatter-merge per-pair top-k lists into the running per-query top-k.

    Each pair lands in merge lane ``(pair_qi, pair_ranks)`` of a
    ``(B+1, n_lanes, k)`` buffer (row B is the dump row for padding pairs),
    then one stable argsort per query takes the new top-k.  Equivalent to
    folding the pairs through sequential stable merges.  The payload
    ``run_p`` (B, k, ...) — ids, or any trailing columns — moves with its
    distance.
    """
    B, k = run_d.shape
    tail = run_p.shape[2:]
    buf_d = jnp.full((B + 1, n_lanes, k), jnp.inf, run_d.dtype)
    buf_p = jnp.full((B + 1, n_lanes, k) + tail, -1, run_p.dtype)
    buf_d = buf_d.at[pair_qi, pair_ranks].set(d)
    buf_p = buf_p.at[pair_qi, pair_ranks].set(p.astype(run_p.dtype))
    all_d = jnp.concatenate([run_d, buf_d[:B].reshape(B, n_lanes * k)], axis=1)
    all_p = jnp.concatenate(
        [run_p, buf_p[:B].reshape((B, n_lanes * k) + tail)], axis=1)
    order = jnp.argsort(all_d, axis=1, stable=True)[:, :k]
    order_p = order.reshape(order.shape + (1,) * len(tail))
    return (jnp.take_along_axis(all_d, order, axis=1),
            jnp.take_along_axis(all_p, order_p, axis=1))


@functools.partial(jax.jit, static_argnames=("dim",))
def gather_rows(vec_buf, rows, *, dim: int):
    """The memory pool's row-granular READ verb: gather exact vector
    rows from the serialized region.  ``rows`` (..., ) region row
    addresses into ``vec_buf.reshape(-1, dim)`` (-1 lanes gather row 0
    and are masked by the caller).  Returns (..., D) f32."""
    with jax.named_scope("rerank/gather_rows"):
        return vec_buf.reshape(-1, dim)[jnp.maximum(rows, 0)]


@functools.partial(jax.jit, static_argnames=("k",))
def rerank_gathered(vrows, queries, rows, gids, *, k: int):
    """Stage 2, compute side: exact distances over already-gathered
    candidate rows (``gather_rows`` is the pool verb that produced
    ``vrows``).  rows (B, m) mark empty lanes with -1; gids (B, m).
    Returns the final (dists (B, k), gids (B, k))."""
    with jax.named_scope("rerank/exact"):
        d = jnp.sum(jnp.square(vrows - queries[:, None, :]), axis=-1)
        d = jnp.where(rows >= 0, d, jnp.inf)
        nd, ni = lax.top_k(-d, k)
        g = jnp.take_along_axis(gids, ni, axis=1)
        return -nd, jnp.where(jnp.isfinite(-nd), g, -1)


@functools.partial(jax.jit, static_argnames=("dim", "group"))
def gather_quant_rows(qvec_buf, qscale_buf, rows, *, dim: int, group: int):
    """Row-granular gather from the QUANTIZED mirror: int8 codes plus the
    per-row codebook scales.  ``rows`` are the same region row addresses
    ``gather_rows`` takes (the mirror shares the block indexing)."""
    safe = jnp.maximum(rows, 0)
    codes = qvec_buf.reshape(-1, dim)[safe]
    scales = qscale_buf.reshape(-1, dim // group)[safe]
    return codes, scales


@functools.partial(jax.jit, static_argnames=("m",))
def flat_candidates(flat_map, d, idx, *, m: int):
    """Stage-1 payload of the flat scan: the kernel's (dists (B, k),
    flat ids (B, k)) -> (pool_d (B, m), pool_p (B, m, 3)).  ``flat_map``
    (N, 3) int32 holds each flat row's (gid, region row, pid); -1 ids
    give (inf, -1 -1 -1) lanes, and k < m lanes are padded the same."""
    with jax.named_scope("stage1/payload"):
        live = idx >= 0
        pool_p = jnp.where(live[..., None], flat_map[jnp.maximum(idx, 0)], -1)
        pool_d = jnp.where(live, d, jnp.inf)
        pad = m - d.shape[1]
        if pad > 0:                    # flat DB smaller than the pool
            pool_d = jnp.pad(pool_d, ((0, 0), (0, pad)),
                             constant_values=jnp.inf)
            pool_p = jnp.pad(pool_p, ((0, 0), (0, pad), (0, 0)),
                             constant_values=-1)
        return pool_d, pool_p


@functools.partial(jax.jit, static_argnames=("spec",))
def overflow_append_quant(spec: LayoutSpec, qvec_buf, qscale_buf, vec,
                          vec_block, vec_off):
    """Device twin of the quantized mirror update for one overflow
    insert: quantize the row in place and scatter codes + codebook
    scales (coords from ``layout.overflow_write_coords``)."""
    from repro.quant.codec import quantize_row_jnp
    g = spec.quant_group
    codes, scales = quantize_row_jnp(vec, g)
    row = lax.dynamic_update_slice(qvec_buf[vec_block], codes, (vec_off,))
    qvec_buf = lax.dynamic_update_index_in_dim(qvec_buf, row, vec_block, 0)
    srow = lax.dynamic_update_slice(qscale_buf[vec_block], scales,
                                    (vec_off // g,))
    qscale_buf = lax.dynamic_update_index_in_dim(qscale_buf, srow,
                                                 vec_block, 0)
    return qvec_buf, qscale_buf


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(1,))
def write_slots(spec: LayoutSpec, cache, slot_ids, blocks):
    """Install fetched spans into cache slots (functional scatter).

    cache: a tier's slot arrays, (g, v) or (g, qv, qs); blocks: the
    matching tuple ``read_spans`` returns, (n_fetch, fetch_blocks, ...)
    each; slot_ids: (n_fetch,).
    """
    with jax.named_scope("fetch/write_slots"):
        return tuple(c.at[slot_ids].set(b) for c, b in zip(cache, blocks))


@functools.partial(jax.jit, static_argnames=("spec",))
def overflow_append(spec: LayoutSpec, graph_buf, vec_buf, vec, gid,
                    vec_block, vec_off, gid_block, gid_off):
    """Device twin of ``layout.insert_vector``: one-slot scatter into the
    shared overflow region (coords from ``overflow_write_coords``)."""
    row = lax.dynamic_update_slice(vec_buf[vec_block], vec, (vec_off,))
    vec_buf = lax.dynamic_update_index_in_dim(vec_buf, row, vec_block, 0)
    grow = graph_buf[gid_block].at[gid_off].set(gid)
    graph_buf = lax.dynamic_update_index_in_dim(graph_buf, grow, gid_block, 0)
    return graph_buf, vec_buf
