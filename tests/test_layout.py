"""RDMA-friendly layout — §3.2: round-trip, spans, overflow, repack.

Includes hypothesis property tests over the layout arithmetic (offsets
never overlap, every span is in-bounds, both partners cover the shared
overflow region).  Without ``hypothesis`` installed the property tests
skip cleanly (``pytest.importorskip``) and the rest of the module runs.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:         # CI fast tier / bare containers
    HAVE_HYPOTHESIS = False

from repro.core import layout as LA
from repro.core.layout import LayoutSpec, build_store
from repro.core.meta import build_meta


@pytest.fixture(scope="module")
def store_and_meta(sift_small):
    meta = build_meta(sift_small.data, 24, seed=2)
    store = build_store(sift_small.data, meta)
    return store, meta, sift_small.data


# ---------------------------------------------------------------- spec math

if HAVE_HYPOTHESIS:
    @given(dim=st.integers(4, 512), deg=st.integers(2, 64),
           np_max=st.integers(1, 3000), ov_cap=st.integers(4, 500),
           slot_vecs=st.integers(1, 128), n_parts=st.integers(1, 600))
    @settings(max_examples=200, deadline=None)
    def test_spec_arithmetic_invariants(dim, deg, np_max, ov_cap, slot_vecs,
                                        n_parts):
        spec = LayoutSpec(dim=dim, deg=deg, np_max=np_max, ov_cap=ov_cap,
                          slot_vecs=slot_vecs, n_partitions=n_parts)
        # capacities: the data span must hold the padded sub-HNSW, the ov
        # span the shared region, in BOTH buffers
        assert spec.data_blocks * spec.gblk >= spec.np_max * (spec.deg + 1)
        assert spec.data_blocks * spec.vblk >= spec.np_max * spec.dim
        assert spec.ov_blocks * spec.gblk >= spec.ov_cap
        assert spec.ov_blocks * spec.vblk >= spec.ov_cap * spec.dim
        assert spec.group_blocks == 2 * spec.data_blocks + spec.ov_blocks
        assert spec.n_blocks == spec.n_groups * spec.group_blocks
        # fetch spans of a group's two partitions: in-bounds, both contain
        # the shared overflow, data regions disjoint
        for pid in (0, 1):
            if pid >= n_parts:
                continue
            start = pid * spec.data_blocks  # side A: 0; side B: data_blocks
            end = start + spec.fetch_blocks
            assert end <= spec.group_blocks
        ov_lo, ov_hi = spec.data_blocks, spec.data_blocks + spec.ov_blocks
        a_span = range(0, spec.fetch_blocks)
        b_span = range(spec.data_blocks, spec.group_blocks)
        assert set(range(ov_lo, ov_hi)) <= set(a_span)
        assert set(range(ov_lo, ov_hi)) <= set(b_span)

    @given(group=st.integers(0, 50), slot=st.integers(0, 199),
           dim=st.integers(4, 256), slot_vecs=st.integers(1, 64))
    @settings(max_examples=200, deadline=None)
    def test_overflow_coords_in_ov_region(group, slot, dim, slot_vecs):
        spec = LayoutSpec(dim=dim, deg=8, np_max=100, ov_cap=200,
                          slot_vecs=slot_vecs, n_partitions=200)
        co = LA.overflow_write_coords(spec, group, slot)
        lo = group * spec.group_blocks + spec.data_blocks
        hi = lo + spec.ov_blocks
        assert lo <= co["vec_block"] < hi
        assert lo <= co["gid_block"] < hi
        # vector writes never straddle a block boundary (vblk % dim == 0)
        assert co["vec_off"] + dim <= spec.vblk
else:
    def test_spec_arithmetic_invariants():
        pytest.importorskip("hypothesis")

    def test_overflow_coords_in_ov_region():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------- round-trip

def test_all_partitions_roundtrip(store_and_meta):
    import jax.numpy as jnp
    from repro.core import device_store as DS
    store, meta, data = store_and_meta
    spec = store.spec
    for pid in range(spec.n_partitions):
        ids = LA.partition_gids(store, pid)
        part, _ = DS.decode_span(
            spec, jnp.asarray(store.graph_buf[store.span_block_ids(pid)]),
            jnp.asarray(store.vec_buf[store.span_block_ids(pid)]),
            jnp.asarray(store.meta_table[pid]))
        n = len(ids)
        assert np.array_equal(np.asarray(part.gids)[:n], ids)
        assert np.allclose(np.asarray(part.vectors)[:n], data[ids])
        assert int(np.asarray(part.valid).sum()) == n


def test_partitions_cover_dataset(store_and_meta):
    store, meta, data = store_and_meta
    allg = np.concatenate([LA.partition_gids(store, p)
                           for p in range(store.spec.n_partitions)])
    assert np.array_equal(np.sort(allg), np.arange(data.shape[0]))


def test_spans_disjoint_data_shared_overflow(store_and_meta):
    store, _, _ = store_and_meta
    spec = store.spec
    seen = {}
    for pid in range(spec.n_partitions):
        span = set(store.span_block_ids(pid).tolist())
        partner = pid ^ 1
        for q, qspan in seen.items():
            inter = span & qspan
            if q == partner:
                assert len(inter) == spec.ov_blocks  # exactly the shared ov
            else:
                assert not inter
        seen[pid] = span


# ---------------------------------------------------------------- insert

def test_insert_into_overflow_and_read_back(store_and_meta):
    import jax.numpy as jnp
    from repro.core import device_store as DS
    store, meta, data = store_and_meta
    spec = store.spec
    pid = 3
    vec = np.float32(np.arange(spec.dim)) / spec.dim
    slot = LA.insert_vector(store, vec, gid=999_999, pid=pid)
    assert slot >= 0
    assert 999_999 in LA.overflow_gids(store, pid).tolist()
    # one contiguous span fetch now returns the inserted vector too
    part, _ = DS.decode_span(
        spec, jnp.asarray(store.graph_buf[store.span_block_ids(pid)]),
        jnp.asarray(store.vec_buf[store.span_block_ids(pid)]),
        jnp.asarray(store.meta_table[pid]))
    gids = np.asarray(part.gids)
    valid = np.asarray(part.valid)
    j = np.nonzero((gids == 999_999) & valid)[0]
    assert len(j) == 1
    assert np.allclose(np.asarray(part.vectors)[j[0]], vec)
    # the PARTNER's fetch must NOT claim this vector as its own
    partner = pid ^ 1
    ppart, _ = DS.decode_span(
        spec, jnp.asarray(store.graph_buf[store.span_block_ids(partner)]),
        jnp.asarray(store.vec_buf[store.span_block_ids(partner)]),
        jnp.asarray(store.meta_table[partner]))
    pg = np.asarray(ppart.gids)
    pv = np.asarray(ppart.valid)
    assert not ((pg == 999_999) & pv).any()


@pytest.fixture(scope="module")
def quant_store_both_sides(sift_small):
    """A store with an int8 mirror and live overflow on BOTH partners of
    group 0 (two inserts from side A, three from side B)."""
    data = sift_small.data[:800]
    meta = build_meta(data, 8, seed=0)
    store = build_store(data, meta, ov_cap=8)
    LA.attach_quant_mirror(store, 32)
    vecs = dict(enumerate(data))
    for j, pid in enumerate((0, 0, 1, 1, 1)):
        vecs[50_000 + j] = data[j] + 0.25
        assert LA.insert_vector(store, vecs[50_000 + j], 50_000 + j, pid) >= 0
    LA.refresh_quant_group(store, 0)
    return store, vecs


@pytest.mark.parametrize("side", [0, 1])
def test_int8_decode_matches_exact_decode(quant_store_both_sides, side):
    """The int8 tier's decode of a span: the exact decode's gids and
    ``valid``, the codec's dequantization of the same rows, and ``rows``
    that address the exact vectors of those gids."""
    import jax.numpy as jnp
    from repro.core import device_store as DS
    from repro.quant.codec import dequantize_groups
    store, vecs = quant_store_both_sides
    spec = store.spec
    pid = side                           # group 0: pid 0 is A, pid 1 is B
    assert int(store.meta_table[pid, LA.MT_SIDE]) == side
    ids = store.span_block_ids(pid)
    g_span = jnp.asarray(store.graph_buf[ids])
    mrow = jnp.asarray(store.meta_table[pid])
    exact, rows_e = DS.decode_span(spec, g_span,
                                   jnp.asarray(store.vec_buf[ids]), mrow)
    quant, rows = DS.decode_span(spec, g_span,
                                 jnp.asarray(store.qvec_buf[ids]), mrow,
                                 jnp.asarray(store.qscale_buf[ids]))
    gids, valid = np.asarray(quant.gids), np.asarray(quant.valid)
    assert np.array_equal(gids, np.asarray(exact.gids))
    assert np.array_equal(valid, np.asarray(exact.valid))
    assert np.array_equal(np.asarray(rows), np.asarray(rows_e))
    # live overflow of this side only: 2 inserts on A, 3 on B
    assert int(valid[spec.np_max:].sum()) == (2, 3)[side]

    live_rows = np.asarray(rows)[valid]
    codes = store.qvec_buf.reshape(-1, spec.dim)[live_rows]
    scales = store.qscale_buf.reshape(-1, spec.dim // 32)[live_rows]
    assert np.array_equal(np.asarray(quant.vectors)[valid],
                          dequantize_groups(codes, scales, 32))
    exact_rows = np.asarray(DS.gather_rows(
        jnp.asarray(store.vec_buf), jnp.asarray(live_rows), dim=spec.dim))
    assert np.array_equal(exact_rows,
                          np.stack([vecs[int(g)] for g in gids[valid]]))


def test_shared_overflow_fills_from_both_ends(sift_small):
    meta = build_meta(sift_small.data[:500], 8, seed=0)
    store = build_store(sift_small.data[:500], meta, ov_cap=6)
    a_pid, b_pid = 0, 1
    v = np.zeros(store.spec.dim, np.float32)
    assert LA.insert_vector(store, v, 10_001, a_pid) == 0
    assert LA.insert_vector(store, v, 10_002, b_pid) == 5
    assert LA.insert_vector(store, v, 10_003, a_pid) == 1
    # counters mirrored on both partners
    assert store.meta_table[a_pid, LA.MT_OV_A] == 2
    assert store.meta_table[b_pid, LA.MT_OV_A] == 2
    assert store.meta_table[a_pid, LA.MT_OV_B] == 1
    # fill it up -> -1 (repack needed)
    for g in range(3):
        LA.insert_vector(store, v, 20_000 + g, a_pid)
    assert LA.insert_vector(store, v, 30_000, a_pid) == -1


def test_repack_group_folds_overflow(sift_small):
    data = sift_small.data[:600]
    meta = build_meta(data, 8, seed=0)
    store = build_store(data, meta, ov_cap=8, np_max=200)
    pid = 2
    extra = {}
    for g in range(4):
        vec = data[g] + 0.01
        extra[1000 + g] = vec
        assert LA.insert_vector(store, vec, 1000 + g, pid) >= 0

    def lookup(gids):
        return np.stack([data[g] if g < 600 else extra[g] for g in gids])

    n_before = int(store.meta_table[pid, LA.MT_N_BASE])
    ok = LA.repack_group(store, int(store.meta_table[pid, LA.MT_GROUP]),
                         lookup)
    assert ok
    assert store.meta_table[pid, LA.MT_OV_A] == 0
    assert store.meta_table[pid, LA.MT_OV_B] == 0
    assert int(store.meta_table[pid, LA.MT_N_BASE]) == n_before + 4
    base = LA.partition_gids(store, pid).tolist()
    for g in extra:
        assert g in base
