"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""
import inspect

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.distance_topk.ops import distance_topk
from repro.kernels.distance_topk.ref import distance_topk_ref
from repro.kernels.gather_blocks.ops import gather_blocks


# ------------------------------------------------------------ distance_topk

@pytest.mark.parametrize("B,N,D,k", [
    (1, 100, 16, 1), (7, 333, 128, 10), (37, 1000, 960, 5),
    (128, 256, 64, 16), (130, 513, 32, 3),
])
def test_distance_topk_sweep(rng, B, N, D, k):
    q = rng.standard_normal((B, D)).astype(np.float32)
    x = rng.standard_normal((N, D)).astype(np.float32)
    d, i = distance_topk(jnp.asarray(q), jnp.asarray(x), k)
    dr, ir = distance_topk_ref(jnp.asarray(q), jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr),
                               atol=1e-2, rtol=1e-4)


@pytest.mark.parametrize("n_valid", [1, 50, 255, 256])
def test_distance_topk_masking(rng, n_valid):
    q = rng.standard_normal((5, 32)).astype(np.float32)
    x = rng.standard_normal((256, 32)).astype(np.float32)
    d, i = distance_topk(jnp.asarray(q), jnp.asarray(x), 8, n_valid=n_valid)
    dr, ir = distance_topk_ref(jnp.asarray(q), jnp.asarray(x), 8,
                               n_valid=n_valid)
    live = np.asarray(i) >= 0
    assert (np.asarray(i)[live] < n_valid).all()
    np.testing.assert_array_equal(np.asarray(i)[live],
                                  np.asarray(ir)[live])
    if n_valid < 8:  # padding semantics: inf/-1 tail
        assert np.isinf(np.asarray(d)[:, n_valid:]).all()


def test_distance_topk_bf16_inputs(rng):
    q = jnp.asarray(rng.standard_normal((9, 64)), jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((300, 64)), jnp.bfloat16)
    d, i = distance_topk(q, x, 5)
    dr, ir = distance_topk_ref(q, x, 5)
    # bf16 ties can reorder; compare sets and values loosely
    same = np.mean([len(set(a.tolist()) & set(b.tolist())) / 5
                    for a, b in zip(np.asarray(i), np.asarray(ir))])
    assert same >= 0.95
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), atol=0.1,
                               rtol=0.02)


# ------------------------------------------------------------- quant_topk

@pytest.mark.parametrize("B,N,D,group,k", [
    (1, 100, 16, 16, 1), (7, 333, 128, 32, 10), (37, 500, 960, 64, 5),
    (128, 256, 64, 32, 16), (130, 513, 32, 8, 3),
])
def test_quant_topk_sweep(rng, B, N, D, group, k):
    from repro.kernels.quant_topk.ops import quant_topk
    from repro.kernels.quant_topk.ref import quant_topk_ref
    from repro.quant.codec import quantize_groups

    q = rng.standard_normal((B, D)).astype(np.float32)
    x = rng.standard_normal((N, D)).astype(np.float32)
    codes, scales = quantize_groups(x, group)
    cj, sj = jnp.asarray(codes), jnp.asarray(scales)
    d, i, _ = quant_topk(jnp.asarray(q), cj, sj, k, group)
    dr, ir = quant_topk_ref(jnp.asarray(q), cj, sj, k, group)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr),
                               atol=1e-2, rtol=1e-4)


@pytest.mark.parametrize("n_valid", [1, 50, 255, 256])
def test_quant_topk_masking(rng, n_valid):
    from repro.kernels.quant_topk.ops import quant_topk
    from repro.kernels.quant_topk.ref import quant_topk_ref
    from repro.quant.codec import quantize_groups

    q = rng.standard_normal((5, 32)).astype(np.float32)
    x = rng.standard_normal((256, 32)).astype(np.float32)
    codes, scales = quantize_groups(x, 8)
    cj, sj = jnp.asarray(codes), jnp.asarray(scales)
    d, i, _ = quant_topk(jnp.asarray(q), cj, sj, 8, 8, n_valid=n_valid)
    dr, ir = quant_topk_ref(jnp.asarray(q), cj, sj, 8, 8, n_valid=n_valid)
    live = np.asarray(i) >= 0
    assert (np.asarray(i)[live] < n_valid).all()
    np.testing.assert_array_equal(np.asarray(i)[live], np.asarray(ir)[live])
    if n_valid < 8:  # padding semantics: inf/-1 tail
        assert np.isinf(np.asarray(d)[:, n_valid:]).all()


def test_quant_topk_close_to_exact(rng):
    """Dequantized distances track the f32 oracle within codec error."""
    from repro.kernels.distance_topk.ref import distance_topk_ref
    from repro.kernels.quant_topk.ops import quant_topk
    from repro.quant.codec import quantize_groups

    q = rng.standard_normal((16, 128)).astype(np.float32)
    x = rng.standard_normal((400, 128)).astype(np.float32)
    codes, scales = quantize_groups(x, 32)
    d, i, _ = quant_topk(jnp.asarray(q), jnp.asarray(codes),
                      jnp.asarray(scales), 10, 32)
    de, ie = distance_topk_ref(jnp.asarray(q), jnp.asarray(x), 10)
    overlap = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                       for a, b in zip(np.asarray(i), np.asarray(ie))])
    assert overlap >= 0.9, overlap
    np.testing.assert_allclose(np.asarray(d), np.asarray(de),
                               rtol=0.05, atol=0.5)


# ------------------------------------------ bounded top-k merge (both kernels)
#
# Databases with a known order, as int8 codes and per-group scales: every
# row is ``s_n * c`` for one code pattern c, so the tile-by-tile insertion
# pattern is known, and the f32 kernel reads the same dequantized rows.

LINE_N, LINE_D, LINE_GROUP, LINE_K = 2048, 32, 8, 20


def _line_db(rng, order: str):
    """(codes, scales, queries, n_live_rows) of a database ordered along
    one line: "desc" (every tile closer than all before it), "asc" (no
    tile after the first holds a closer row), "vee" (asc for the live
    queries, but closer rows in later tiles for a zero query row)."""
    c = rng.integers(1, 4, size=LINE_D).astype(np.int8)
    n = np.arange(LINE_N)
    s = {"desc": LINE_N - n + 10.0, "asc": n + 10.0,
         "vee": n - LINE_N / 2.0}[order]
    codes = (np.sign(s)[:, None] * c[None, :]).astype(np.int8)
    scales = np.repeat(np.abs(s)[:, None], LINE_D // LINE_GROUP, axis=1)
    offset = 2.0 * LINE_N if order == "vee" else 0.0
    e = rng.integers(-1, 2, size=(64, LINE_D)).astype(np.float32)
    queries = e - offset * c.astype(np.float32)
    return codes, scales.astype(np.float32), queries


def _tie_db(rng, n=1000, d=16, group=8):
    """Small-integer rows at scale 1: every distance is an exact small
    integer, so ties at the k-th boundary are everywhere."""
    codes = rng.integers(-2, 3, size=(n, d)).astype(np.int8)
    scales = np.ones((n, d // group), np.float32)
    queries = rng.integers(-2, 3, size=(64, d)).astype(np.float32)
    return codes, scales, queries


def _db(rng, case):
    if case == "ties":
        return _tie_db(rng) + (8,)
    return _line_db(rng, case) + (LINE_GROUP,)


@pytest.mark.parametrize("case", ["desc", "asc", "ties"])
def test_quant_topk_ordered_databases(rng, case):
    from repro.kernels.quant_topk.ops import quant_topk
    from repro.kernels.quant_topk.ref import quant_topk_ref

    codes, scales, q, group = _db(rng, case)
    args = (jnp.asarray(q), jnp.asarray(codes), jnp.asarray(scales),
            LINE_K, group)
    d, i, _ = quant_topk(*args)
    dr, ir = quant_topk_ref(*args)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=1e-6)


@pytest.mark.parametrize("case", ["desc", "asc", "ties"])
def test_distance_topk_ordered_databases(rng, case):
    from repro.kernels.quant_topk.ref import dequantize_ref

    codes, scales, q, group = _db(rng, case)
    x = dequantize_ref(jnp.asarray(codes), jnp.asarray(scales), group)
    d, i = distance_topk(jnp.asarray(q), x, LINE_K)
    dr, ir = distance_topk_ref(jnp.asarray(q), x, LINE_K)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=1e-6)


@pytest.mark.parametrize("n_valid", [300, 700, 1500])
def test_quant_topk_n_valid_mid_tile(rng, n_valid):
    """The masked tail holds the closest rows of a descending database:
    none of them may surface."""
    from repro.kernels.quant_topk.ops import quant_topk
    from repro.kernels.quant_topk.ref import quant_topk_ref

    codes, scales, q = _line_db(rng, "desc")
    args = (jnp.asarray(q), jnp.asarray(codes), jnp.asarray(scales),
            LINE_K, LINE_GROUP)
    d, i, _ = quant_topk(*args, n_valid=n_valid)
    dr, ir = quant_topk_ref(*args, n_valid=n_valid)
    assert (np.asarray(i) < n_valid).all()
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


@pytest.mark.parametrize("B", [5, 130])
def test_quant_topk_padded_query_rows(rng, B):
    """Padded query rows change no live row's answer and add no round:
    on "vee" a zero query row would insert in the middle tiles, the live
    rows only in the first tile of each q-tile."""
    from repro.kernels.quant_topk.ops import quant_topk
    from repro.kernels.quant_topk.ref import quant_topk_ref

    codes, scales, q = _line_db(rng, "vee")
    q = np.concatenate([q, q, q])[:B]
    args = (jnp.asarray(q), jnp.asarray(codes), jnp.asarray(scales),
            LINE_K, LINE_GROUP)
    d, i, work = quant_topk(*args)
    dr, ir = quant_topk_ref(*args)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    q_tiles = -(-B // 128)
    assert np.asarray(work).tolist() == [LINE_K * q_tiles,
                                         q_tiles * _tiles(quant_topk)]


def _tiles(quant_topk):
    """Database tiles of one q-tile over LINE_N rows at the default size."""
    return LINE_N // inspect.signature(quant_topk).parameters[
        "block_n"].default


@pytest.mark.parametrize("case", ["desc", "asc"])
def test_quant_topk_counts_merge_rounds(rng, case):
    """Rounds follow the insertions: k in every tile of a descending
    database, k in the first tile and none after on an ascending one."""
    from repro.kernels.quant_topk.ops import quant_topk

    codes, scales, q = _line_db(rng, case)
    _, _, work = quant_topk(jnp.asarray(q), jnp.asarray(codes),
                            jnp.asarray(scales), LINE_K, LINE_GROUP)
    tiles = _tiles(quant_topk)
    assert tiles >= 2
    rounds = LINE_K * tiles if case == "desc" else LINE_K
    assert np.asarray(work).tolist() == [rounds, tiles]
    _, _, ref_work = quant_topk(jnp.asarray(q), jnp.asarray(codes),
                                jnp.asarray(scales), LINE_K, LINE_GROUP,
                                use_ref=True)
    assert np.asarray(ref_work).tolist() == [0, 0]


# ------------------------------------------------------------ gather_blocks

@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("m", [1, 5, 64])
def test_gather_blocks_sweep(rng, dtype, m):
    buf = (rng.standard_normal((40, 192)) * 100).astype(dtype)
    ids = rng.integers(0, 40, m).astype(np.int32)
    out = gather_blocks(jnp.asarray(buf), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(out), buf[ids])


def test_gather_blocks_repeated_ids(rng):
    buf = rng.standard_normal((16, 64)).astype(np.float32)
    ids = np.array([3, 3, 3, 0, 15, 3], np.int32)
    out = gather_blocks(jnp.asarray(buf), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(out), buf[ids])


# --------------------------------------------------------- decode_attention

@pytest.mark.parametrize("B,S,K,G,hd", [
    (1, 256, 1, 1, 64), (3, 512, 4, 2, 64), (2, 1024, 2, 8, 128),
    (5, 300, 6, 1, 32),
])
def test_decode_attention_sweep(rng, B, S, K, G, hd):
    q = rng.standard_normal((B, K * G, hd)).astype(np.float32)
    k = rng.standard_normal((B, S, K, hd)).astype(np.float32)
    v = rng.standard_normal((B, S, K, hd)).astype(np.float32)
    pos = rng.integers(1, S + 1, B).astype(np.int32)
    o = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         jnp.asarray(pos))
    orf = decode_attention_ref(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               atol=2e-5, rtol=1e-4)


def test_decode_attention_bf16(rng):
    B, S, K, G, hd = 2, 256, 2, 2, 64
    q = jnp.asarray(rng.standard_normal((B, K * G, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.bfloat16)
    pos = jnp.asarray([100, 256], jnp.int32)
    o = decode_attention(q, k, v, pos)
    orf = decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(o, dtype=np.float32),
                               np.asarray(orf, dtype=np.float32),
                               atol=0.02, rtol=0.02)


def test_decode_attention_pos_zero_edge(rng):
    """pos=1: only the first cache entry attended."""
    B, S, K, G, hd = 1, 256, 1, 1, 32
    q = rng.standard_normal((B, K * G, hd)).astype(np.float32)
    k = rng.standard_normal((B, S, K, hd)).astype(np.float32)
    v = rng.standard_normal((B, S, K, hd)).astype(np.float32)
    pos = np.array([1], np.int32)
    o = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(o)[0, 0], v[0, 0, 0], atol=1e-5)
