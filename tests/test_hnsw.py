"""Host HNSW: recall vs brute force, bulk L0 build, graph invariants."""
import numpy as np
import pytest

from repro.core.hnsw import (HNSW, HNSWParams, brute_force_knn,
                             bulk_l0_graph, recall_at_k)

# long-running tier: excluded from CI fast job (-m 'not slow')
pytestmark = pytest.mark.slow


def test_brute_force_is_exact(rng):
    data = rng.standard_normal((500, 16)).astype(np.float32)
    q = rng.standard_normal((7, 16)).astype(np.float32)
    d, i = brute_force_knn(data, q, 5)
    # exhaustively check one query
    full = np.sum((data - q[0]) ** 2, axis=1)
    assert set(i[0].tolist()) == set(np.argsort(full)[:5].tolist())
    assert np.all(np.diff(d, axis=1) >= -1e-5)  # sorted ascending


def test_hnsw_recall_beats_random(rng):
    data = rng.standard_normal((2000, 32)).astype(np.float32)
    queries = data[:50] + 0.01 * rng.standard_normal((50, 32)).astype(np.float32)
    _, gt = brute_force_knn(data, queries, 10)
    h = HNSW(32, HNSWParams(M=8, M0=16, ef_construction=64)).build(data)
    pred = np.array([[i for _, i in h.search(q, 10, ef=64)] for q in queries])
    rec = recall_at_k(pred, gt)
    assert rec >= 0.9, rec


def test_hnsw_recall_monotone_in_ef(rng):
    data = rng.standard_normal((1500, 24)).astype(np.float32)
    queries = data[:40] + 0.01 * rng.standard_normal((40, 24)).astype(np.float32)
    _, gt = brute_force_knn(data, queries, 10)
    h = HNSW(24, HNSWParams(M=8, M0=16, ef_construction=48)).build(data)
    recs = []
    for ef in (10, 32, 96):
        pred = np.array([[i for _, i in h.search(q, 10, ef=ef)]
                         for q in queries])
        recs.append(recall_at_k(pred, gt))
    assert recs[-1] >= recs[0] - 0.02, recs  # allow tiny noise
    assert recs[-1] >= 0.85


def test_export_shapes(rng):
    data = rng.standard_normal((300, 8)).astype(np.float32)
    h = HNSW(8, HNSWParams(M=4, M0=8)).build(data)
    g = h.export()
    assert g.vectors.shape == (300, 8)
    assert g.adjacency.shape[1] == 300 and g.adjacency.shape[2] == 8
    assert g.adjacency.min() >= -1 and g.adjacency.max() < 300
    # every live node has at least one neighbor at L0
    deg = (g.adjacency[0] >= 0).sum(1)
    assert (deg[1:] > 0).all()


def test_bulk_l0_graph_properties(rng):
    v = rng.standard_normal((400, 16)).astype(np.float32)
    adj = bulk_l0_graph(v, 8)
    assert adj.shape == (400, 8)
    assert adj.max() < 400
    # no self-edges, padded with -1 only at the tail of each row
    for i in range(0, 400, 37):
        row = adj[i]
        live = row[row >= 0]
        assert i not in live
        assert len(set(live.tolist())) == len(live)


def test_bulk_graph_greedy_search_recall(rng):
    """Beam search over the bulk graph reaches true neighbors."""
    import jax.numpy as jnp
    from repro.core.search import batched_beam_search
    v = rng.standard_normal((800, 16)).astype(np.float32)
    adj = bulk_l0_graph(v, 12)
    queries = v[:30] + 0.01 * rng.standard_normal((30, 16)).astype(np.float32)
    _, gt = brute_force_knn(v, queries, 5)
    d, i = batched_beam_search(jnp.asarray(v), jnp.asarray(adj[None]),
                               jnp.asarray(queries), 0, ef=48)
    rec = recall_at_k(np.asarray(i)[:, :5], gt)
    assert rec >= 0.85, rec


def _per_node_prune_ref(v, m0):
    """The HNSW neighbor heuristic one node and one pair at a time: the
    reference the vectorized ``bulk_l0_graph`` must match exactly."""
    n = v.shape[0]
    k = min(2 * m0 + 1, n)
    x2 = np.einsum("nd,nd->n", v, v)
    d = x2[None, :] - 2.0 * v @ v.T + x2[:, None]
    d[np.arange(n), np.arange(n)] = np.inf
    idx = np.argpartition(d, k - 1, axis=1)[:, :k]
    dd = np.take_along_axis(d, idx, axis=1)
    order = np.argsort(dd, axis=1)
    idx = np.take_along_axis(idx, order, axis=1)
    dd = np.take_along_axis(dd, order, axis=1)
    adj = np.full((n, m0), -1, np.int32)
    for node in range(n):
        kept = []
        for dq, c in zip(dd[node], idx[node]):
            if len(kept) >= m0:
                break
            if all(float(np.sum(np.square(v[c] - v[j]))) >= dq
                   for j in kept):
                kept.append(int(c))
        for c in idx[node]:
            if len(kept) >= m0:
                break
            if int(c) not in kept:
                kept.append(int(c))
        adj[node, :len(kept)] = kept
    nbrs = [[c for c in row if c >= 0] for row in adj.tolist()]
    for node in range(n):
        for c in list(nbrs[node]):
            if len(nbrs[c]) < m0 and node not in nbrs[c]:
                nbrs[c].append(node)
    for node, lst in enumerate(nbrs):
        adj[node, :len(lst)] = lst
    return adj


@pytest.mark.parametrize("n,dim,dist_elems", [
    (2, 8, 2**26), (5, 16, 2**26), (33, 16, 2**26), (600, 128, 2**26),
    (200, 960, 2**26), (1100, 128, 1000 * 1100)])
def test_bulk_l0_graph_matches_per_node_heuristic(rng, monkeypatch, n, dim,
                                                  dist_elems):
    """Clustered rows make near-ties common; every keep/prune decision
    must still be the per-pair one (tiny n also covers the self edge, a
    small distance budget the row chunks that large partitions take)."""
    from repro.core import hnsw
    monkeypatch.setattr(hnsw, "_DIST_ELEMS", dist_elems)
    centers = rng.random((4, dim), dtype=np.float32)
    v = (centers[rng.integers(0, 4, n)]
         + 0.15 * rng.standard_normal((n, dim)).astype(np.float32))
    np.testing.assert_array_equal(bulk_l0_graph(v, 16),
                                  _per_node_prune_ref(v, 16))
