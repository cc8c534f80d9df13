"""Query-aware batched loading — §3.3 invariants (+hypothesis).

The property tests need ``hypothesis``; when it isn't installed they
skip cleanly (``pytest.importorskip``) and the deterministic invariant
tests still run.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:         # CI fast tier / bare containers
    HAVE_HYPOTHESIS = False

from repro.core.scheduler import (LRUCacheState, TieredCacheState,
                                  doorbell_chunks_sharded, naive_plan,
                                  plan_batch)


def _random_topb(rng, B, b, P):
    out = np.zeros((B, b), np.int64)
    for q in range(B):
        out[q] = rng.choice(P, size=b, replace=False)
    return out


def test_each_partition_loaded_at_most_once():
    """The paper's headline invariant: one load per partition per batch."""
    rng = np.random.default_rng(0)
    topb = _random_topb(rng, 50, 3, 40)
    plan = plan_batch(topb, LRUCacheState(8), doorbell=4)
    loads = plan.loads_per_partition()
    assert all(v == 1 for v in loads.values()), loads
    assert plan.n_fetches == len(plan.unique_pids)


def test_resident_partitions_not_fetched():
    rng = np.random.default_rng(1)
    cache = LRUCacheState(16)
    topb = _random_topb(rng, 30, 2, 20)
    p1 = plan_batch(topb, cache, doorbell=4)
    # same batch again: everything needed should be cache-hit or refetch
    p2 = plan_batch(topb, cache, doorbell=4)
    assert p2.n_fetches < p1.n_fetches  # warm cache saved transfers
    assert p2.n_cache_hits > 0


def test_every_query_served_for_every_needed_partition():
    rng = np.random.default_rng(2)
    topb = _random_topb(rng, 25, 3, 30)
    plan = plan_batch(topb, LRUCacheState(6), doorbell=4)
    served = set()
    for rnd in plan.rounds:
        for q, p in rnd.serve_pairs:
            served.add((int(q), int(p)))
    want = {(q, int(p)) for q in range(25) for p in topb[q]}
    assert served == want


def test_rounds_respect_cache_capacity():
    rng = np.random.default_rng(3)
    cap = 5
    topb = _random_topb(rng, 40, 4, 60)
    plan = plan_batch(topb, LRUCacheState(cap), doorbell=3)
    for rnd in plan.rounds:
        assert len(rnd.fetch_pids) <= cap
        assert len(set(rnd.fetch_slots.tolist())) == len(rnd.fetch_pids)
        for db in rnd.doorbells:
            assert len(db) <= 3


def test_sharded_doorbell_chunks_never_mix_destinations():
    """Descriptor batches are formed per destination shard: every chunk
    is single-owner, <= doorbell long, and the union is the input."""
    items = np.arange(17, dtype=np.int64)
    owner = lambda p: p % 3                               # noqa: E731
    chunks = doorbell_chunks_sharded(items, 4, owner)
    seen = []
    for db in chunks:
        assert len(db) <= 4
        assert len({owner(int(x)) for x in db}) == 1
        seen.extend(int(x) for x in db)
    assert sorted(seen) == items.tolist()
    # owner_of=None degrades to plain sequential chunking
    plain = doorbell_chunks_sharded(items, 4, None)
    assert [len(c) for c in plain] == [4, 4, 4, 4, 1]
    # plan_batch threads the owner through to each round's doorbells
    rng = np.random.default_rng(9)
    plan = plan_batch(_random_topb(rng, 30, 4, 50), LRUCacheState(6),
                      doorbell=4, owner_of=owner)
    for rnd in plan.rounds:
        for db in rnd.doorbells:
            assert len({owner(int(x)) for x in db}) == 1


def test_naive_plan_counts_all_pairs():
    rng = np.random.default_rng(4)
    topb = _random_topb(rng, 10, 3, 50)
    raw = naive_plan(topb)
    assert len(raw) == 30  # no dedup across queries (only within)


def test_serve_ranks_unique_per_query_per_round():
    """The merge lanes the device scatter relies on: within a round, a
    query's pairs occupy ranks 0..m-1 exactly once each."""
    rng = np.random.default_rng(6)
    topb = _random_topb(rng, 30, 4, 25)
    plan = plan_batch(topb, LRUCacheState(6), doorbell=4)
    for rnd in plan.rounds:
        assert len(rnd.pair_ranks) == len(rnd.serve_pairs)
        per_q = {}
        for (q, _), r in zip(rnd.serve_pairs, rnd.pair_ranks):
            per_q.setdefault(int(q), []).append(int(r))
        for ranks in per_q.values():
            assert sorted(ranks) == list(range(len(ranks)))
        assert rnd.n_lanes == max((len(v) for v in per_q.values()),
                                  default=1)
        # padded batch-major view round-trips
        n = len(rnd.serve_pairs)
        qi, pids, slots, ranks, valid = rnd.serve_tensors(n + 3, 30)
        assert valid[:n].all() and not valid[n:].any()
        assert (qi[n:] == 30).all()
        assert np.array_equal(qi[:n], rnd.serve_pairs[:, 0])
        assert np.array_equal(pids[:n], rnd.serve_pairs[:, 1])
        assert np.array_equal(slots[:n], rnd.pair_slots)


if HAVE_HYPOTHESIS:
    @given(B=st.integers(1, 40), b=st.integers(1, 5), P=st.integers(5, 64),
           cap=st.integers(2, 20), doorbell=st.integers(1, 8),
           seed=st.integers(0, 100))
    @settings(max_examples=80, deadline=None)
    def test_plan_invariants_property(B, b, P, cap, doorbell, seed):
        rng = np.random.default_rng(seed)
        b = min(b, P)
        topb = _random_topb(rng, B, b, P)
        cache = LRUCacheState(cap)
        plan = plan_batch(topb, cache, doorbell=doorbell)
        # 1. at most one load per partition
        assert all(v == 1 for v in plan.loads_per_partition().values())
        # 2. slots valid and unique within every round
        for rnd in plan.rounds:
            assert len(rnd.fetch_pids) <= cap
            assert all(0 <= s < cap for s in rnd.fetch_slots)
            assert len(set(rnd.fetch_slots.tolist())) == len(rnd.fetch_slots)
            # pairs of a round reference partitions fetched-or-resident
            # with the recorded slots
            for (q, p), s in zip(rnd.serve_pairs, rnd.pair_slots):
                assert 0 <= s < cap
        # 3. every (query, needed-partition) pair served exactly once
        served = [(int(q), int(p)) for rnd in plan.rounds
                  for q, p in rnd.serve_pairs]
        want = sorted({(q, int(p)) for q in range(B) for p in topb[q]})
        assert sorted(served) == want
        # 4. cache never over-full after the batch
        assert len(cache.resident()) <= cap
else:
    def test_plan_invariants_property():
        pytest.importorskip("hypothesis")


def test_lru_eviction_order():
    c = LRUCacheState(2)
    c.admit(1)
    c.admit(2)
    c.touch(1)            # 2 is now LRU
    slot, ev = c.admit(3)
    assert ev == 2
    assert c.resident() == {1, 3}


def test_lru_capacity_one_thrash():
    """cap=1 is pure thrash: every distinct admit evicts the previous
    pid into the same slot, and planning still covers every pair."""
    c = LRUCacheState(1)
    s0, e0 = c.admit(7)
    assert (s0, e0) == (0, -1)
    s1, e1 = c.admit(9)
    assert (s1, e1) == (0, 7)
    s2, e2 = c.admit(9)          # re-admit resident: no eviction
    assert (s2, e2) == (0, -1)
    assert c.resident() == {9}

    rng = np.random.default_rng(9)
    topb = _random_topb(rng, 12, 3, 10)
    plan = plan_batch(topb, LRUCacheState(1), doorbell=2)
    served = {(int(q), int(p)) for r in plan.rounds for q, p in r.serve_pairs}
    assert served == {(q, int(p)) for q in range(12) for p in topb[q]}
    for rnd in plan.rounds:
        assert len(rnd.fetch_pids) <= 1
        assert all(s == 0 for s in rnd.fetch_slots)


def test_lru_drop_then_readmit():
    """drop() (the engine's insert invalidation) frees the slot and the
    next plan refetches the pid into a valid slot."""
    c = LRUCacheState(2)
    c.admit(4)
    c.admit(5)
    c.drop(4)
    assert c.resident() == {5}
    assert 4 not in c._recency
    c.drop(4)                    # idempotent on non-resident pids
    slot, ev = c.admit(4)        # re-admit fills the freed slot
    assert ev == -1 and c.resident() == {4, 5}
    plan = plan_batch(np.array([[4], [5]]), c, doorbell=1)
    assert plan.n_fetches == 0   # both resident again -> pure hits


def test_engine_readmits_after_invalidate_pid(built_engine, sift_small):
    """After an insert invalidates a cached partition, the next search
    must refetch it (no stale serve) and return identical results."""
    q = sift_small.queries[:8]
    d0, g0, _ = built_engine.search(q, k=10)
    _, _, warm = built_engine.search(q, k=10)
    resident = sorted(built_engine.cache.resident())
    assert resident, "warm cache expected"
    built_engine._invalidate_pid(resident[0])
    _, _, st = built_engine.search(q, k=10)
    assert st["n_fetches"] >= 1          # the dropped pid was refetched
    d1, g1, _ = built_engine.search(q, k=10)
    assert np.array_equal(g0, g1)
    assert np.array_equal(d0, d1)


# ------------------------------------------------------------ tiered cache

def test_tiered_cache_invalidate_drops_both_tiers():
    t = TieredCacheState(4, 2)
    t.quant.admit(3)
    t.exact.admit(3)
    t.note_rerank_miss(3, 100)
    t.invalidate(3)
    assert 3 not in t.quant.resident()
    assert 3 not in t.exact.resident()
    assert t._miss_rows.get(3) is None


def test_tiered_cache_cost_based_admission():
    t = TieredCacheState(4, 1)
    row_b, span_b = 512, 10 * 512
    t.note_rerank_miss(1, 4)
    assert not t.should_admit(1, row_b, span_b)   # 4 rows < 10-row span
    t.note_rerank_miss(1, 7)
    assert t.should_admit(1, row_b, span_b)       # cumulative 11 >= 10
    t.admit_exact(1)
    assert not t.should_admit(1, row_b, span_b)   # resident: never again
    # evicting 1 decays (not erases) its counter
    t._miss_rows[1] = 6.0            # stale traffic from while resident
    t.note_rerank_miss(2, 20)
    _, ev = t.admit_exact(2)
    assert ev == 1
    assert t._miss_rows[1] == 6.0 * TieredCacheState.DECAY


# ------------------------------------------- merge_ranked vs numpy oracle

def _numpy_fold_merge(run_d, run_g, qi, d, g):
    """The pre-vectorization semantics: fold each pair into its query's
    running top-k through a sequential stable merge."""
    want_d, want_g = run_d.copy(), run_g.copy()
    k = run_d.shape[1]
    for j in range(len(qi)):
        q = int(qi[j])
        md = np.concatenate([want_d[q], d[j]])
        mg = np.concatenate([want_g[q], g[j]])
        order = np.argsort(md, kind="stable")[:k]
        want_d[q], want_g[q] = md[order], mg[order]
    return want_d, want_g


if HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("width", [None, 3],
                             ids=["ids", "payload3"])
    @given(seed=st.integers(0, 10_000), B=st.just(9), k=st.just(8),
           n=st.just(21))
    @settings(max_examples=40, deadline=None)
    def test_merge_ranked_matches_numpy_fold(width, seed, B, k, n):
        """Property: the fused device scatter-merge == the numpy
        sequential fold, ties included (fixed shapes -> one XLA compile
        across all examples), for a (B, k) id list and for the int8
        tier's (B, k, 3) [gid, row, pid] payload."""
        import jax.numpy as jnp

        from repro.core.device_store import merge_ranked
        from repro.core.scheduler import _pair_ranks

        tail = () if width is None else (width,)
        rng = np.random.default_rng(seed)
        run_d = np.sort(rng.standard_normal((B, k)).astype(np.float32) ** 2,
                        axis=1)
        run_g = rng.integers(0, 1000, (B, k) + tail).astype(np.int32)
        qi = rng.integers(0, B, n)
        d = np.sort(rng.standard_normal((n, k)).astype(np.float32) ** 2,
                    axis=1)
        if n and rng.random() < 0.5:     # force exact cross-list ties
            d[0] = run_d[int(qi[0])]
        g = rng.integers(1000, 2000, (n, k) + tail).astype(np.int32)

        want_d, want_g = _numpy_fold_merge(run_d, run_g, qi, d, g)
        ranks = _pair_ranks(np.stack([qi, np.zeros(n, np.int64)], axis=1))
        got_d, got_g = merge_ranked(
            jnp.asarray(run_d), jnp.asarray(run_g),
            jnp.asarray(qi, jnp.int32), jnp.asarray(ranks, jnp.int32),
            jnp.asarray(d), jnp.asarray(g),
            n_lanes=int(ranks.max()) + 1 if n else 1)
        assert np.array_equal(np.asarray(got_d), want_d)
        assert np.array_equal(np.asarray(got_g), want_g)
else:
    @pytest.mark.parametrize("width", [None, 3],
                             ids=["ids", "payload3"])
    def test_merge_ranked_matches_numpy_fold(width):
        pytest.importorskip("hypothesis")
