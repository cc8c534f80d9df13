"""Device-side store: fetched-span decode + per-partition search.

Everything here is static-shaped and jit-friendly.  A fetch span is
``(fetch_blocks, gblk)`` int32 + ``(fetch_blocks, vblk)`` float32 — the
unit one doorbell descriptor covers.  ``decode_span`` turns a span + its
metadata row into padded search arrays; the two search paths (faithful
graph walk / MXU scan) run on the decoded view.

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


def decode_span(spec: LayoutSpec, g_span, v_span, meta_row) -> DecodedPartition:
    """g_span (fetch_blocks, gblk) i32; v_span (fetch_blocks, vblk) f32."""
    side = meta_row[MT_SIDE]
    n_base = meta_row[MT_N_BASE]
    gflat = g_span.reshape(-1)
    vflat = v_span.reshape(-1)

    data_g = lax.dynamic_slice(gflat, (side * spec.ov_blocks * spec.gblk,),
                               (spec.np_max * (spec.deg + 1),))
    adjacency = data_g[: spec.np_max * spec.deg].reshape(spec.np_max, spec.deg)
    base_gids = data_g[spec.np_max * spec.deg:]

    ov_goff = (1 - side) * spec.data_blocks * spec.gblk
    ov_gids = lax.dynamic_slice(gflat, (ov_goff,), (spec.ov_cap,))

    data_v = lax.dynamic_slice(vflat, (side * spec.ov_blocks * spec.vblk,),
                               (spec.np_max * spec.dim,))
    base_vecs = data_v.reshape(spec.np_max, spec.dim)
    ov_voff = (1 - side) * spec.data_blocks * spec.vblk
    ov_vecs = lax.dynamic_slice(vflat, (ov_voff,),
                                (spec.ov_cap * spec.dim,)).reshape(
                                    spec.ov_cap, spec.dim)

    cnt_a, cnt_b = meta_row[MT_OV_A], meta_row[MT_OV_B]
    ov_idx = jnp.arange(spec.ov_cap)
    # A's inserts fill the front, B's fill the back; a fetch sees both but
    # only its own side's slots belong to this partition
    ov_mine = jnp.where(side == 0, ov_idx < cnt_a,
                        ov_idx >= spec.ov_cap - cnt_b)
    base_valid = jnp.arange(spec.np_max) < n_base
    return DecodedPartition(
        vectors=jnp.concatenate([base_vecs, ov_vecs], axis=0),
        adjacency=adjacency[None],
        gids=jnp.concatenate([base_gids, ov_gids]),
        valid=jnp.concatenate([base_valid, ov_mine]),
        entry=meta_row[MT_ENTRY],
    )


def search_decoded_scan(part: DecodedPartition, q, k: int):
    """Exact top-k over every valid vector (base + overflow) — the
    beyond-paper MXU path.  Returns (dists (k,), global ids (k,))."""
    d = jnp.sum(jnp.square(part.vectors - q[None, :]), axis=-1)
    d = jnp.where(part.valid, d, jnp.inf)
    nd, ni = lax.top_k(-d, k)
    return -nd, part.gids[ni]


def search_decoded_graph(part: DecodedPartition, q, k: int, ef: int):
    """Paper-faithful: beam-search the sub-HNSW graph over the base
    vectors, then brute-scan the (tiny) live overflow slice and merge —
    exactly how the paper covers not-yet-relinked inserted vectors."""
    np_max = part.adjacency.shape[1]
    bd, bi = S.beam_search(part.vectors[:np_max], part.adjacency, q,
                           part.entry, ef=max(ef, k), n_levels=1)
    bd = jnp.where((bi >= 0) & part.valid[jnp.maximum(bi, 0)], bd, jnp.inf)
    base_d, base_i = bd[:k], jnp.where(jnp.isfinite(bd[:k]),
                                       part.gids[jnp.maximum(bi[:k], 0)], -1)
    ov_vecs = part.vectors[np_max:]
    ov_d = jnp.sum(jnp.square(ov_vecs - q[None, :]), axis=-1)
    ov_d = jnp.where(part.valid[np_max:], ov_d, jnp.inf)
    kk = min(k, ov_vecs.shape[0])
    od, oi = lax.top_k(-ov_d, kk)
    og = part.gids[np_max + oi]
    return S.merge_topk(base_d, base_i, -od, jnp.where(jnp.isfinite(-od), og, -1), k)


@functools.partial(jax.jit,
                   static_argnames=("spec", "k", "ef", "mode", "n_lanes"),
                   donate_argnums=(5, 6))
def serve_and_merge(spec: LayoutSpec, cache_g, cache_v, meta_table, queries,
                    run_d, run_g, pair_qi, pair_pids, pair_slots, pair_ranks,
                    pair_valid, *, k: int, ef: int, mode: str, n_lanes: int):
    """One round, fused: per-pair top-k inside the pair's partition, then a
    single vectorized scatter-merge into the batch's running top-k.

    Replaces the host loop that merged each pair's ``(k,)`` list into its
    query's running list one ``np.argsort`` at a time.  All staging is
    device-side gathers from arrays resident since batch start:

    meta_table: (n_partitions, META_COLS) — the whole cached table; each
                pair gathers its own row (no per-round host rebuild)
    queries:    (B, D) — the full query batch; gathered by ``pair_qi``
    run_d/run_g:(B, k) running top-k carried across rounds (donated)
    pair_qi:    (n_pairs,) query index; padding lanes point at row B so
                the ``(B+1, n_lanes, k)`` scatter drops them
    pair_ranks: (n_pairs,) merge lane — occurrence index of the pair's
                query within this round (unique per (query, round))
    Returns the updated (run_d, run_g): (B, k) each.

    Merge semantics are identical to folding the pairs in order through a
    stable sort (stable argsort over [running | lane 0 | lane 1 | ...] is
    associative with the sequential stable merges the host loop did), so
    results are bit-identical to the old path.
    """
    with jax.named_scope("serve/decode"):
        rows = meta_table[pair_pids]
        qs = queries[pair_qi]      # padding qi == B clamps; masked below

    def one(slot, row, q, ok):
        with jax.named_scope("serve/decode"):
            part = decode_span(spec, cache_g[slot], cache_v[slot], row)
        with jax.named_scope("serve/walk"):
            if mode == "graph":
                d, g = search_decoded_graph(part, q, k, ef)
            else:
                d, g = search_decoded_scan(part, q, k)
            return jnp.where(ok, d, jnp.inf), jnp.where(ok, g, -1)

    d, g = jax.vmap(one)(pair_slots, rows, qs, pair_valid)
    with jax.named_scope("serve/merge"):
        return merge_ranked(run_d, run_g, pair_qi, pair_ranks, d, g,
                            n_lanes=n_lanes)


@functools.partial(jax.jit, static_argnames=("n_lanes",))
def merge_ranked(run_d, run_g, pair_qi, pair_ranks, d, g, *, n_lanes: int):
    """Scatter-merge per-pair top-k lists into the running per-query top-k.

    Each pair lands in merge lane ``(pair_qi, pair_ranks)`` of a
    ``(B+1, n_lanes, k)`` buffer (row B is the dump row for padding pairs),
    then one stable argsort per query takes the new top-k.  Equivalent to
    folding the pairs through sequential stable merges.
    """
    k = run_d.shape[1]
    B = run_d.shape[0]
    buf_d = jnp.full((B + 1, n_lanes, k), jnp.inf, run_d.dtype)
    buf_g = jnp.full((B + 1, n_lanes, k), -1, run_g.dtype)
    buf_d = buf_d.at[pair_qi, pair_ranks].set(d)
    buf_g = buf_g.at[pair_qi, pair_ranks].set(g.astype(run_g.dtype))
    all_d = jnp.concatenate([run_d, buf_d[:B].reshape(B, n_lanes * k)], axis=1)
    all_g = jnp.concatenate([run_g, buf_g[:B].reshape(B, n_lanes * k)], axis=1)
    order = jnp.argsort(all_d, axis=1, stable=True)[:, :k]
    return (jnp.take_along_axis(all_d, order, axis=1),
            jnp.take_along_axis(all_g, order, axis=1))


# ------------------------------------------------------------ quantized tier
#
# The staged (quant=int8) search path: stage 1 decodes QUANTIZED spans
# resident in the large quantized tier into the same DecodedPartition
# view (dequantize = one fused multiply) and pools per-query candidates
# (distance, gid, exact-row address, pid); stage 2 gathers only the
# candidate rows in full precision and re-ranks to the final top-k.
# Everything below is additive — the full-precision serve path above is
# untouched so quant="none" stays bit-identical.


def decode_quant_span(spec: LayoutSpec, g_span, qv_span, qs_span, meta_row):
    """Quantized twin of ``decode_span``.

    g_span (fetch_blocks, gblk) i32; qv_span (fetch_blocks, vblk) int8;
    qs_span (fetch_blocks, n_qgroups) f32.  Returns (DecodedPartition
    with dequantized f32 vectors, rows (np_max + ov_cap,) i32) where
    ``rows`` are exact-row addresses into ``vec_buf.reshape(-1, dim)``
    — what stage 2 fetches for re-ranking.
    """
    g = spec.quant_group
    side = meta_row[MT_SIDE]
    n_base = meta_row[MT_N_BASE]
    gflat = g_span.reshape(-1)
    qvflat = qv_span.reshape(-1).astype(jnp.float32)
    qsflat = qs_span.reshape(-1)

    data_g = lax.dynamic_slice(gflat, (side * spec.ov_blocks * spec.gblk,),
                               (spec.np_max * (spec.deg + 1),))
    adjacency = data_g[: spec.np_max * spec.deg].reshape(spec.np_max, spec.deg)
    base_gids = data_g[spec.np_max * spec.deg:]
    ov_goff = (1 - side) * spec.data_blocks * spec.gblk
    ov_gids = lax.dynamic_slice(gflat, (ov_goff,), (spec.ov_cap,))

    def dequant(flat_off_floats, n_vecs):
        codes = lax.dynamic_slice(qvflat, (flat_off_floats,),
                                  (n_vecs * spec.dim,))
        scales = lax.dynamic_slice(qsflat, (flat_off_floats // g,),
                                   (n_vecs * spec.dim // g,))
        x = codes.reshape(-1, g) * scales[:, None]
        return x.reshape(n_vecs, spec.dim)

    base_vecs = dequant(side * spec.ov_blocks * spec.vblk, spec.np_max)
    ov_vecs = dequant((1 - side) * spec.data_blocks * spec.vblk, spec.ov_cap)

    cnt_a, cnt_b = meta_row[MT_OV_A], meta_row[MT_OV_B]
    ov_idx = jnp.arange(spec.ov_cap)
    ov_mine = jnp.where(side == 0, ov_idx < cnt_a,
                        ov_idx >= spec.ov_cap - cnt_b)
    base_valid = jnp.arange(spec.np_max) < n_base

    # exact-row addresses: vblk = slot_vecs * dim, so row r of the region
    # lives at flat row index block * slot_vecs + local offset
    blk_start = meta_row[MT_BLK_START]
    data_row0 = (blk_start + side * spec.ov_blocks) * spec.slot_vecs
    ov_row0 = (blk_start + (1 - side) * spec.data_blocks) * spec.slot_vecs
    rows = jnp.concatenate([data_row0 + jnp.arange(spec.np_max),
                            ov_row0 + jnp.arange(spec.ov_cap)]).astype(
                                jnp.int32)

    part = DecodedPartition(
        vectors=jnp.concatenate([base_vecs, ov_vecs], axis=0),
        adjacency=adjacency[None],
        gids=jnp.concatenate([base_gids, ov_gids]),
        valid=jnp.concatenate([base_valid, ov_mine]),
        entry=meta_row[MT_ENTRY],
    )
    return part, rows


def _pad_topk(d, i, k: int):
    """Pad a (kk,) top list to (k,) with inf/-1 when kk < k."""
    kk = d.shape[0]
    if kk >= k:
        return d[:k], i[:k]
    pad = k - kk
    return (jnp.concatenate([d, jnp.full((pad,), jnp.inf, d.dtype)]),
            jnp.concatenate([i, jnp.full((pad,), -1, i.dtype)]))


def search_decoded_scan_local(part: DecodedPartition, q, k: int):
    """Like ``search_decoded_scan`` but returns LOCAL indices (the
    candidate-pool path needs them to derive exact-row addresses)."""
    n = part.vectors.shape[0]
    d = jnp.sum(jnp.square(part.vectors - q[None, :]), axis=-1)
    d = jnp.where(part.valid, d, jnp.inf)
    nd, ni = lax.top_k(-d, min(k, n))
    return _pad_topk(-nd, ni.astype(jnp.int32), k)


def search_decoded_graph_local(part: DecodedPartition, q, k: int, ef: int):
    """Like ``search_decoded_graph`` but returns LOCAL indices: beam walk
    over the base graph + brute scan of the live overflow slice."""
    np_max = part.adjacency.shape[1]
    bd, bi = S.beam_search(part.vectors[:np_max], part.adjacency, q,
                           part.entry, ef=max(ef, k), n_levels=1)
    bd = jnp.where((bi >= 0) & part.valid[jnp.maximum(bi, 0)], bd, jnp.inf)
    ov_d = jnp.sum(jnp.square(part.vectors[np_max:] - q[None, :]), axis=-1)
    ov_d = jnp.where(part.valid[np_max:], ov_d, jnp.inf)
    all_d = jnp.concatenate([bd, ov_d])
    all_i = jnp.concatenate([bi.astype(jnp.int32),
                             np_max + jnp.arange(ov_d.shape[0],
                                                 dtype=jnp.int32)])
    kk = min(k, all_d.shape[0])
    nd, pos = lax.top_k(-all_d, kk)
    return _pad_topk(-nd, all_i[pos], k)


@functools.partial(jax.jit,
                   static_argnames=("spec", "m", "ef", "mode", "n_lanes"),
                   donate_argnums=(6, 7))
def serve_quant_pool(spec: LayoutSpec, cache_qg, cache_qv, cache_qs,
                     meta_table, queries, pool_d, pool_p, pair_qi,
                     pair_pids, pair_slots, pair_ranks, pair_valid, *,
                     m: int, ef: int, mode: str, n_lanes: int):
    """Stage-1 round, fused: per-pair top-m inside the pair's QUANTIZED
    partition, then one scatter-merge into the batch's running candidate
    pool.  ``pool_d`` (B, m) distances; ``pool_p`` (B, m, 3) int32
    payload columns [gid, exact_row, pid] carried through the merge.
    """
    mrows = meta_table[pair_pids]
    qs = queries[pair_qi]

    def one(slot, mrow, q, ok, pid):
        part, rows = decode_quant_span(spec, cache_qg[slot], cache_qv[slot],
                                       cache_qs[slot], mrow)
        if mode == "graph":
            d, li = search_decoded_graph_local(part, q, m, ef)
        else:
            d, li = search_decoded_scan_local(part, q, m)
        live = (li >= 0) & ok & jnp.isfinite(d)
        safe = jnp.maximum(li, 0)
        payload = jnp.stack([
            jnp.where(live, part.gids[safe], -1),
            jnp.where(live, rows[safe], -1),
            jnp.where(live, pid, -1),
        ], axis=-1).astype(jnp.int32)
        return jnp.where(live, d, jnp.inf), payload

    d, p = jax.vmap(one)(pair_slots, mrows, qs, pair_valid, pair_pids)
    return merge_ranked_payload(pool_d, pool_p, pair_qi, pair_ranks, d, p,
                                n_lanes=n_lanes)


@functools.partial(jax.jit, static_argnames=("n_lanes",))
def merge_ranked_payload(run_d, run_p, pair_qi, pair_ranks, d, p, *,
                         n_lanes: int):
    """``merge_ranked`` with an (…, P) int payload instead of a single id
    column — same (B+1, n_lanes, m) scatter + one stable argsort per
    query, so round grouping never changes the merged result."""
    B, m = run_d.shape
    P = run_p.shape[2]
    buf_d = jnp.full((B + 1, n_lanes, m), jnp.inf, run_d.dtype)
    buf_p = jnp.full((B + 1, n_lanes, m, P), -1, run_p.dtype)
    buf_d = buf_d.at[pair_qi, pair_ranks].set(d)
    buf_p = buf_p.at[pair_qi, pair_ranks].set(p.astype(run_p.dtype))
    all_d = jnp.concatenate([run_d, buf_d[:B].reshape(B, n_lanes * m)],
                            axis=1)
    all_p = jnp.concatenate([run_p, buf_p[:B].reshape(B, n_lanes * m, P)],
                            axis=1)
    order = jnp.argsort(all_d, axis=1, stable=True)[:, :m]
    return (jnp.take_along_axis(all_d, order, axis=1),
            jnp.take_along_axis(all_p, order[:, :, None], axis=1))


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


def rerank_exact(vec_buf, queries, rows, gids, *, dim: int, k: int):
    """Fused legacy entry point: gather + re-rank in one call (kept for
    callers that hold the region buffer directly; the engine now splits
    this across the pool boundary as gather_rows -> rerank_gathered)."""
    vrows = gather_rows(vec_buf, rows, dim=dim)
    return rerank_gathered(vrows, queries, rows, gids, k=k)


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


@functools.partial(jax.jit, static_argnames=("spec",),
                   donate_argnums=(1, 2, 3))
def write_slots_quant(spec: LayoutSpec, cache_qg, cache_qv, cache_qs,
                      slot_ids, g_blocks, qv_blocks, qs_blocks):
    """Install fetched QUANTIZED spans into quant-tier slots."""
    cache_qg = cache_qg.at[slot_ids].set(g_blocks)
    cache_qv = cache_qv.at[slot_ids].set(qv_blocks)
    cache_qs = cache_qs.at[slot_ids].set(qs_blocks)
    return cache_qg, cache_qv, cache_qs


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


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(1, 2))
def write_slots(spec: LayoutSpec, cache_g, cache_v, slot_ids, g_blocks,
                v_blocks):
    """Install fetched spans into cache slots (functional scatter).

    g_blocks: (n_fetch, fetch_blocks, gblk); slot_ids: (n_fetch,).
    """
    with jax.named_scope("fetch/write_slots"):
        cache_g = cache_g.at[slot_ids].set(g_blocks)
        cache_v = cache_v.at[slot_ids].set(v_blocks)
        return cache_g, cache_v


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
