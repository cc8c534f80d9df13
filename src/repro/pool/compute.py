"""ComputeClient — the compute-pool node of the disaggregated system.

Owns exactly what the paper lets a compute instance hold: the cached
representative meta-HNSW (§3.1), the resident-partition cache tiers
(§3.3, exact and/or quantized), the round scheduler, and the device
serve kernels.  Every byte of index data it touches arrives through a
``MemoryPool`` verb (``pool/protocol.py``) — span reads, row reads, and
one-sided appends — so swapping the transport (in-process, simulated
RDMA, and later a real fabric) never changes a line here.

``core/engine.py DHNSWEngine`` is a thin facade over (ComputeClient +
pool); the search/insert bodies below are the engine's previous
monolithic paths re-expressed on the boundary, kept bit-identical for
``pool="local"``.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device_store as DS
from repro.core import layout as LA
from repro.core import meta as ME
from repro.core import scheduler as SCH
from repro.core import search as S
from repro.core.cost_model import NetLedger
from repro.core.hnsw import HNSWParams
from repro.core.scheduler import pow2_pad
from repro.obs.trace import TRACER
from repro.pool.protocol import MemoryPool


class ComputeClient:
    """Plans greedy search against a ``MemoryPool`` (build once, then
    ``search``/``insert`` batches).

    Each host step is a ``TRACER.span`` (``compute.route``,
    ``compute.plan``, ``compute.fetch``, ``compute.serve``, ...).  The
    ``compute.fetch`` and ``compute.serve`` spans, like the ``sub_s``
    seconds around the serve rounds, time the host's dispatch of the
    device work: JAX returns before the device is done.  The device's
    own time is under the ``fetch/`` and ``serve/`` scopes of the jitted
    bodies, in a profiler trace."""

    def __init__(self, cfg, pool_factory):
        self.cfg = cfg
        self._pool_factory = pool_factory   # Store -> MemoryPool
        self.pool: Optional[MemoryPool] = None
        self.meta: Optional[ME.MetaIndex] = None
        self.tiers: Optional[SCH.TieredCacheState] = None
        self._extra: dict[int, np.ndarray] = {}   # inserted gid -> vector
        self._extra_pid: dict[int, int] = {}
        self._n0 = 0                              # base dataset size
        self._data: Optional[np.ndarray] = None
        self._last_insert_net: Optional[dict] = None
        # dense-resident flat stage-1 state (quant_kernel route)
        self._flat_synced = False
        self._flat_idx = None

    @property
    def store(self):
        """The pool's host ``Store`` (compat view for tests/benchmarks)."""
        return self.pool.store

    # ------------------------------------------------------------ build

    def build(self, data: np.ndarray) -> "ComputeClient":
        """Partition ``data``, build the meta-HNSW + serialized region,
        hand the region to the pool, and warm the compute-side caches."""
        cfg = self.cfg
        data = np.asarray(data, np.float32)
        self._data = data
        self._n0 = data.shape[0]
        self.meta = ME.build_meta(data, cfg.n_rep, seed=cfg.seed,
                                  meta_levels=cfg.meta_levels)
        store = LA.build_store(
            data, self.meta,
            sub_params=HNSWParams(M=max(cfg.sub_M0 // 2, 2), M0=cfg.sub_M0,
                                  ef_construction=cfg.ef_construction))
        self._adopt(store)
        return self

    def adopt_built(self, meta: ME.MetaIndex, store,
                    data: np.ndarray) -> "ComputeClient":
        """Wire a meta + region built elsewhere (the streaming
        ``repro.ingest.BulkLoader``) into the client and warm the same
        caches ``build`` would.  ``data`` backs repack/rebuild lookups
        and may be a read-only disk-backed view (np.memmap) — the
        builder never needs the full dataset resident."""
        self._data = data
        self._n0 = data.shape[0]
        self.meta = meta
        self._adopt(store)
        return self

    def _adopt(self, store) -> None:
        """Shared tail of ``build``/``adopt_built``: hand the region to
        the pool and warm the compute-side caches."""
        cfg = self.cfg
        self.pool = self._pool_factory(store)
        # compute pool (cached, replicated): the meta-HNSW
        self._meta_vecs = jnp.asarray(self.meta.graph.vectors)
        self._meta_adj = jnp.asarray(self.meta.graph.adjacency)
        self._meta_entry = int(self.meta.graph.entry)
        cap = max(2, int(np.ceil(cfg.cache_frac * self.meta.n_partitions)))
        self._cap0 = cap
        self._setup_caches(cap)

    def _setup_caches(self, cap: int):
        cfg = self.cfg
        if cfg.quant == "none":
            self.tiers = None
            self.cache = SCH.LRUCacheState(cap)
            spec = self.pool.spec
            self._cache_g = jnp.full((cap, spec.fetch_blocks, spec.gblk), -1,
                                     jnp.int32)
            self._cache_v = jnp.zeros((cap, spec.fetch_blocks, spec.vblk),
                                      jnp.float32)
        else:
            self._setup_quant(cap)
        self._flat_synced = False

    def _setup_quant(self, cap: int):
        """Attach the int8 mirror and size the two device tiers from the
        SAME byte budget a quant="none" engine would spend on ``cap``
        full-precision slots: a small exact tier (``exact_frac`` of the
        budget) plus a quantized tier filling the remainder — ~3-4x the
        partitions per byte, so stage-1 hits replace remote reads."""
        cfg = self.cfg
        st = self.pool.store
        if (st.qvec_buf is not None
                and st.spec.quant_group == cfg.quant_group):
            # the loader (or a previous attach) already built the mirror
            # host-side with the same codec geometry — stage it, don't
            # re-quantize the whole region
            self.pool._stage_quant()
        else:
            self.pool.attach_quant(cfg.quant_group)
        spec = self.pool.spec
        pb = spec.partition_bytes()
        qpb = spec.quant_partition_bytes(
            include_graph=cfg.search_mode == "graph")
        exact_cap = max(1, int(round(cap * cfg.exact_frac)))
        quant_cap = max(2, int((cap - exact_cap) * pb // qpb))
        self.tiers = SCH.TieredCacheState(quant_cap, exact_cap)
        self.cache = self.tiers.exact   # legacy helpers see the exact tier
        self._cache_g = jnp.full((exact_cap, spec.fetch_blocks, spec.gblk),
                                 -1, jnp.int32)
        self._cache_v = jnp.zeros((exact_cap, spec.fetch_blocks, spec.vblk),
                                  jnp.float32)
        self._cache_qg = jnp.full((quant_cap, spec.fetch_blocks, spec.gblk),
                                  -1, jnp.int32)
        self._cache_qv = jnp.zeros((quant_cap, spec.fetch_blocks, spec.vblk),
                                   jnp.int8)
        self._cache_qs = jnp.zeros(
            (quant_cap, spec.fetch_blocks, spec.n_qgroups), jnp.float32)

    def _lookup(self, gids: np.ndarray) -> np.ndarray:
        out = np.zeros((len(gids), self.pool.spec.dim), np.float32)
        for i, g in enumerate(int(x) for x in gids):
            out[i] = self._data[g] if g < self._n0 else self._extra[g]
        return out

    # ------------------------------------------------------------ search

    def _route(self, q_dev, b: int):
        """Meta-HNSW routing — cached in the compute pool, no network."""
        pids, _ = S.meta_route(self._meta_vecs, self._meta_adj, q_dev,
                               self._meta_entry, b=b,
                               n_levels=self.meta.graph.n_levels)
        return np.asarray(jax.block_until_ready(pids))

    def search(self, queries: np.ndarray, k: int = 10,
               ef: Optional[int] = None, b: Optional[int] = None):
        """Batched top-k.  Returns (dists (B,k), gids (B,k), stats)."""
        cfg = self.cfg
        ef = ef or cfg.ef
        b = b or cfg.b
        if cfg.quant != "none":
            return self._search_quant(queries, k=k, ef=ef, b=b)
        pool = self.pool
        spec = pool.spec
        queries = np.asarray(queries, np.float32)
        B = queries.shape[0]
        q_dev = jnp.asarray(queries)
        ledger = NetLedger(cfg.fabric)
        stats = {"meta_s": 0.0, "sub_s": 0.0, "plan_s": 0.0,
                 "n_rounds": 0, "n_pairs": 0}

        t0 = time.perf_counter()
        with TRACER.span("compute.route", tier="compute", B=B):
            pids = self._route(q_dev, b)
        stats["meta_s"] = time.perf_counter() - t0

        # plan (compute-instance CPU role)
        t0 = time.perf_counter()
        with TRACER.span("compute.plan", tier="compute") as span:
            owner_of = getattr(pool, "owner_of_pid", None)
            if cfg.mode == "naive":
                raw = SCH.naive_plan(pids)
                # every pair is its own READ round trip (the 3.547 trips/
                # query); dedup below is compute-only, so movement through
                # the pool goes uncharged (ledger=None) — already posted
                pool.post_span_reads(len(raw), ledger=ledger, doorbell=1,
                                     pids=[p for _, p in raw])
                uniq = sorted({p for _, p in raw})
                cache = SCH.LRUCacheState(max(len(uniq), 1))
                plan = SCH.plan_batch(pids, cache, doorbell=1)
            else:
                plan = SCH.plan_batch(pids, self.cache,
                                      doorbell=cfg.doorbell,
                                      owner_of=owner_of)
            span.set(rounds=len(plan.rounds), fetches=plan.n_fetches,
                     hits=plan.n_cache_hits)
        stats["plan_s"] = time.perf_counter() - t0

        # rounds: fetch -> serve -> merge (all device-side; the running
        # top-k is carried as (B, k) device arrays and each round folds
        # in with ONE fused scatter-merge — no host loop over pairs)
        mt_dev = pool.read_meta()
        run_d = jnp.full((B, k), jnp.inf, jnp.float32)
        run_g = jnp.full((B, k), -1, jnp.int32)
        cache_state = cache if cfg.mode == "naive" else self.cache
        if cfg.mode == "naive":
            cache_g = jnp.full((cache_state.capacity, spec.fetch_blocks,
                                spec.gblk), -1, jnp.int32)
            cache_v = jnp.zeros((cache_state.capacity, spec.fetch_blocks,
                                 spec.vblk), jnp.float32)
            fetch_ledger = None          # naive pre-charged every demand
            fetch_doorbell = 1
        else:
            cache_g, cache_v = self._cache_g, self._cache_v
            fetch_ledger = ledger
            fetch_doorbell = 1 if cfg.mode == "no_doorbell" else cfg.doorbell

        for rnd in plan.rounds:
            stats["n_rounds"] += 1
            with TRACER.span("compute.round", tier="compute",
                             fetch=int(len(rnd.fetch_pids)),
                             pairs=int(len(rnd.serve_pairs))):
                if len(rnd.fetch_pids):
                    with TRACER.span("compute.fetch", tier="compute",
                                     spans=int(len(rnd.fetch_pids))):
                        g_blocks, v_blocks = pool.read_spans(
                            rnd.fetch_pids, ledger=fetch_ledger,
                            doorbell=fetch_doorbell)
                        slots = jnp.asarray(rnd.fetch_slots, jnp.int32)
                        cache_g, cache_v = DS.write_slots(
                            spec, cache_g, cache_v, slots, g_blocks,
                            v_blocks)
                if not len(rnd.serve_pairs):
                    continue
                t0 = time.perf_counter()
                n = len(rnd.serve_pairs)
                with TRACER.span("compute.serve", tier="compute", pairs=n):
                    npad = pow2_pad(n)
                    qi, ppid, pslot, prank, valid = rnd.serve_tensors(npad,
                                                                      B)
                    # n_lanes is fixed at b (a query never has more than
                    # b pairs in one round) so recompiles depend only on
                    # (B, npad)
                    run_d, run_g = DS.serve_and_merge(
                        spec, cache_g, cache_v, mt_dev, q_dev, run_d, run_g,
                        jnp.asarray(qi), jnp.asarray(ppid),
                        jnp.asarray(pslot), jnp.asarray(prank),
                        jnp.asarray(valid), k=k, ef=ef,
                        mode=cfg.search_mode, n_lanes=b)
                stats["sub_s"] += time.perf_counter() - t0
                stats["n_pairs"] += n

        t0 = time.perf_counter()
        run_d = np.asarray(jax.block_until_ready(run_d))
        run_g = np.asarray(run_g).astype(np.int64)
        stats["sub_s"] += time.perf_counter() - t0
        if cfg.mode != "naive":
            self._cache_g, self._cache_v = cache_g, cache_v
        stats["net"] = ledger.as_dict()
        stats["round_trips_per_query"] = ledger.round_trips / max(B, 1)
        stats["cache_hits"] = plan.n_cache_hits
        stats["n_fetches"] = plan.n_fetches
        stats["pool"] = pool.snapshot()
        return run_d, run_g, stats

    # ------------------------------------------------------ staged search

    def _search_quant(self, queries: np.ndarray, k: int, ef: int, b: int):
        """Two-stage search over the quantized resident tier.

        Stage 1 plans against the LARGE quantized tier (same §3.3 round
        machinery, same doorbell batching — misses move int8 codes +
        codebook blocks, ~1/3-1/4 the bytes of an exact span) and pools
        per-query top-m candidates with their exact-row addresses.
        Stage 2 fetches ONLY the candidate rows in full precision
        (rows in exact-tier-resident partitions are free) and re-ranks.
        When the quantized tier is dense-resident (it can hold every
        partition) and the in-partition search is the flat scan, stage 1
        routes through the fused ``quant_topk`` Pallas kernel instead
        (``_stage1_flat``); the per-pair jnp path is the fallback.
        """
        cfg = self.cfg
        pool = self.pool
        spec = pool.spec
        include_graph = cfg.search_mode == "graph"
        pb = spec.partition_bytes()
        qpb = spec.quant_partition_bytes(include_graph=include_graph)
        row_b = spec.row_bytes()
        m = max(int(cfg.rerank_m) or 2 * k, k)
        queries = np.asarray(queries, np.float32)
        B = queries.shape[0]
        q_dev = jnp.asarray(queries)
        ledger = NetLedger(cfg.fabric)
        stats = {"meta_s": 0.0, "sub_s": 0.0, "plan_s": 0.0,
                 "n_rounds": 0, "n_pairs": 0, "quant": cfg.quant,
                 "rerank_m": m}

        if self._flat_kernel_active():
            pool_d, pool_p, plan = self._stage1_flat(q_dev, B, m, ledger,
                                                     stats)
            tiers = self.tiers
        else:
            pool_d, pool_p, plan, tiers = self._stage1_pairs(
                q_dev, B, m, ef, b, qpb, pb, ledger, stats)

        # stage-2 accounting: pool payload -> row fetch plan
        t0 = time.perf_counter()
        pool_p = jax.block_until_ready(pool_p)
        stats["sub_s"] += time.perf_counter() - t0
        t0 = time.perf_counter()
        with TRACER.span("compute.rerank_plan", tier="compute") as span:
            pool_h = np.asarray(pool_p)
            live = pool_h[:, :, 1] >= 0
            flat_rows = pool_h[:, :, 1][live]
            flat_pids = pool_h[:, :, 2][live]
            n_admitted = 0
            if cfg.mode == "naive":
                # every (query, row) need is its own remote read (real pids
                # so a sharded pool can attribute each to its destination)
                pool.post_row_reads([(int(p), 1) for p in flat_pids],
                                    ledger=ledger, doorbell=1)
                stats["rerank_rows"] = int(len(flat_rows))
                stats["rerank_hit_rows"] = 0
            else:
                # query-aware: each needed row moves at most once per batch
                uniq_rows, first = np.unique(flat_rows, return_index=True)
                uniq_pids = flat_pids[first]
                resident = tiers.exact.resident()
                hit = np.isin(uniq_pids, np.fromiter(resident, np.int64,
                                                     len(resident)))
                groups: dict[int, int] = {}
                for p in uniq_pids[~hit].tolist():
                    groups[p] = groups.get(p, 0) + 1
                items = sorted(groups.items())
                pool.post_row_reads(
                    items, ledger=ledger,
                    doorbell=1 if cfg.mode == "no_doorbell" else cfg.doorbell)
                if items:
                    ledger.save(pb * len(items)
                                - sum(c for _, c in items) * row_b)
                for p in set(uniq_pids[hit].tolist()):
                    tiers.exact.touch(int(p))
                # cost-based admission: a partition whose cumulative missed
                # re-rank rows already outweigh one span fetch is promoted
                for p, cnt in items:
                    tiers.note_rerank_miss(int(p), cnt)
                    if tiers.should_admit(int(p), row_b, pb):
                        slot, _ = tiers.admit_exact(int(p))
                        g_b, v_b = pool.read_spans(np.array([int(p)]),
                                                   ledger=ledger, doorbell=1)
                        self._cache_g, self._cache_v = DS.write_slots(
                            spec, self._cache_g, self._cache_v,
                            jnp.asarray([slot], jnp.int32), g_b, v_b)
                        n_admitted += 1
                stats["rerank_rows"] = int((~hit).sum())
                stats["rerank_hit_rows"] = int(hit.sum())
            span.set(admitted=n_admitted)
        stats["plan_s"] += time.perf_counter() - t0
        stats["exact_admitted"] = n_admitted

        # stage-2 re-rank: exact distances over candidate rows only
        t0 = time.perf_counter()
        with TRACER.span("compute.rerank", tier="compute", m=m):
            vrows = pool.read_rows(pool_p[:, :, 1])
            run_d, run_g = DS.rerank_gathered(vrows, q_dev, pool_p[:, :, 1],
                                              pool_p[:, :, 0], k=k)
            run_d = np.asarray(jax.block_until_ready(run_d))
        run_g = np.asarray(run_g).astype(np.int64)
        stats["sub_s"] += time.perf_counter() - t0

        stats["net"] = ledger.as_dict()
        stats["round_trips_per_query"] = ledger.round_trips / max(B, 1)
        stats["cache_hits"] = plan["n_cache_hits"]
        stats["n_fetches"] = plan["n_fetches"]
        stats["pool"] = pool.snapshot()
        return run_d, run_g, stats

    def _stage1_pairs(self, q_dev, B: int, m: int, ef: int, b: int,
                      qpb: int, pb: int, ledger, stats):
        """Per-pair stage 1 (the jnp fallback): plan against the
        quantized tier with the §3.3 round machinery and pool top-m
        candidates via fused per-round scatter-merges."""
        cfg = self.cfg
        pool = self.pool
        spec = pool.spec
        include_graph = cfg.search_mode == "graph"

        t0 = time.perf_counter()
        with TRACER.span("compute.route", tier="compute", B=B):
            pids = self._route(q_dev, b)
        stats["meta_s"] = time.perf_counter() - t0

        # stage-1 plan against the quantized tier.  A quantized span
        # read moves the codes + codebook (and, in graph mode, the
        # adjacency blocks): 2 descriptors per span
        t0 = time.perf_counter()
        with TRACER.span("compute.plan", tier="compute") as span:
            if cfg.mode == "naive":
                raw = SCH.naive_plan(pids)
                pool.post_span_reads(len(raw), ledger=ledger, doorbell=1,
                                     quant=True, quant_graph=include_graph,
                                     pids=[p for _, p in raw])
                ledger.save(len(raw) * (pb - qpb))
                uniq = sorted({p for _, p in raw})
                tiers = SCH.TieredCacheState(max(len(uniq), 1), 1)
                plan = SCH.plan_batch(pids, tiers.quant, doorbell=1)
            else:
                tiers = self.tiers
                plan = SCH.plan_batch(pids, tiers.quant,
                                      doorbell=cfg.doorbell,
                                      owner_of=getattr(pool, "owner_of_pid",
                                                       None))
            span.set(rounds=len(plan.rounds), fetches=plan.n_fetches,
                     hits=plan.n_cache_hits)
        stats["plan_s"] = time.perf_counter() - t0

        # stage-1 rounds: fetch quantized spans -> pool candidates
        mt_dev = pool.read_meta()
        pool_d = jnp.full((B, m), jnp.inf, jnp.float32)
        pool_p = jnp.full((B, m, 3), -1, jnp.int32)
        if cfg.mode == "naive":
            qcap = tiers.quant.capacity
            cache_qg = jnp.full((qcap, spec.fetch_blocks, spec.gblk), -1,
                                jnp.int32)
            cache_qv = jnp.zeros((qcap, spec.fetch_blocks, spec.vblk),
                                 jnp.int8)
            cache_qs = jnp.zeros((qcap, spec.fetch_blocks, spec.n_qgroups),
                                 jnp.float32)
            fetch_ledger = None
            fetch_doorbell = 1
        else:
            cache_qg, cache_qv, cache_qs = (self._cache_qg, self._cache_qv,
                                            self._cache_qs)
            fetch_ledger = ledger
            fetch_doorbell = 1 if cfg.mode == "no_doorbell" else cfg.doorbell

        for rnd in plan.rounds:
            stats["n_rounds"] += 1
            with TRACER.span("compute.round", tier="compute",
                             fetch=int(len(rnd.fetch_pids)),
                             pairs=int(len(rnd.serve_pairs))):
                if len(rnd.fetch_pids):
                    with TRACER.span("compute.fetch", tier="compute",
                                     spans=int(len(rnd.fetch_pids)),
                                     quant=True):
                        g_blocks, qv_blocks, qs_blocks = pool.read_spans(
                            rnd.fetch_pids, ledger=fetch_ledger,
                            doorbell=fetch_doorbell, quant=True,
                            quant_graph=include_graph)
                        if fetch_ledger is not None:
                            ledger.save(len(rnd.fetch_pids) * (pb - qpb))
                        slots = jnp.asarray(rnd.fetch_slots, jnp.int32)
                        cache_qg, cache_qv, cache_qs = DS.write_slots_quant(
                            spec, cache_qg, cache_qv, cache_qs, slots,
                            g_blocks, qv_blocks, qs_blocks)
                if not len(rnd.serve_pairs):
                    continue
                t0 = time.perf_counter()
                n = len(rnd.serve_pairs)
                with TRACER.span("compute.serve", tier="compute", pairs=n,
                                 quant=True):
                    npad = pow2_pad(n)
                    qi, ppid, pslot, prank, valid = rnd.serve_tensors(npad,
                                                                      B)
                    pool_d, pool_p = DS.serve_quant_pool(
                        spec, cache_qg, cache_qv, cache_qs, mt_dev, q_dev,
                        pool_d, pool_p, jnp.asarray(qi), jnp.asarray(ppid),
                        jnp.asarray(pslot), jnp.asarray(prank),
                        jnp.asarray(valid), m=m, ef=max(ef, m),
                        mode=cfg.search_mode, n_lanes=b)
                stats["sub_s"] += time.perf_counter() - t0
                stats["n_pairs"] += n
        if cfg.mode != "naive":
            self._cache_qg, self._cache_qv, self._cache_qs = (
                cache_qg, cache_qv, cache_qs)
        return pool_d, pool_p, {"n_cache_hits": plan.n_cache_hits,
                                "n_fetches": plan.n_fetches}, tiers

    # ------------------------------------------------ flat stage-1 (kernel)

    def _flat_kernel_active(self) -> bool:
        """The quant_topk route: only for flat (scan) stage 1, and only
        when the quantized tier is dense-resident — it can hold every
        partition, so after one sweep the whole int8 database lives at
        the compute node and stage 1 never touches the wire again."""
        cfg = self.cfg
        return (cfg.quant_kernel != "off" and cfg.search_mode == "scan"
                and self.tiers is not None
                and self.tiers.quant.capacity >= self.pool.spec.n_partitions)

    def _sync_flat(self, ledger) -> None:
        """Populate (or refresh) the dense-resident flat view.

        Cold sync charges one quantized-span read per partition,
        doorbell-batched — the same bytes the per-pair path would pay to
        warm a tier of this size.  Afterwards the view stays coherent
        for free on inserts (the writer already holds the rows it
        appends — its own one-sided WRITE moved them); repacks and
        rebuilds force a full resync.
        """
        cfg = self.cfg
        spec = self.pool.spec
        self.pool.post_span_reads(
            spec.n_partitions, ledger=ledger,
            doorbell=1 if cfg.mode in ("naive", "no_doorbell")
            else cfg.doorbell,
            quant=True, quant_graph=False,
            pids=np.arange(spec.n_partitions))
        rows, gids, pids = LA.flat_quant_rows(self.pool.store)
        n = len(rows)
        npad = pow2_pad(max(n, 1), lo=256)
        self._flat_idx = np.full(npad, -1, np.int64)
        self._flat_idx[:n] = rows
        self._flat_gid = np.full(npad, -1, np.int64)
        self._flat_gid[:n] = gids
        self._flat_pid = np.full(npad, -1, np.int64)
        self._flat_pid[:n] = pids
        self._flat_n = n
        codes, scales = self.pool.read_quant_rows(
            jnp.asarray(self._flat_idx, jnp.int32))
        self._flat_codes = jax.block_until_ready(codes)
        self._flat_scales = scales
        # mark every partition resident so insert invalidation (drop)
        # has something to invalidate -> forces a resync
        for p in range(spec.n_partitions):
            self.tiers.quant.admit(p)
        self._flat_synced = True

    def _stage1_flat(self, q_dev, B: int, m: int, ledger, stats):
        """Stage 1 as ONE fused int8 scan: ``quant_topk`` (Pallas on
        real accelerators; under ``quant_kernel="auto"`` the jnp ref on
        CPU, where Pallas would interpret) over the flat dense-resident
        database.
        No meta routing, no rounds — every live row is a candidate, so
        recall is bounded below by the per-pair path at equal m."""
        from repro.kernels.quant_topk.ops import auto_use_ref, quant_topk

        cfg = self.cfg
        # "ref" forces the jnp oracle everywhere; "auto" picks it only
        # where Pallas would run interpreted (CPU), and Pallas elsewhere
        use_ref = (cfg.quant_kernel == "ref"
                   or (cfg.quant_kernel == "auto" and auto_use_ref()))
        t0 = time.perf_counter()
        cold = not self._flat_synced
        if cold:
            with TRACER.span("compute.flat_sync", tier="compute"):
                self._sync_flat(ledger)
            ledger.save(self.pool.spec.n_partitions
                        * (self.pool.spec.partition_bytes()
                           - self.pool.spec.quant_partition_bytes(
                               include_graph=False)))
        stats["plan_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        with TRACER.span("compute.stage1_flat", tier="compute",
                         rows=int(self._flat_n), B=B):
            d, idx, work = quant_topk(q_dev, self._flat_codes,
                                      self._flat_scales, min(m, self._flat_n),
                                      cfg.quant_group, n_valid=self._flat_n,
                                      use_ref=use_ref)
            work.copy_to_host_async()
            d, idx = jax.block_until_ready((d, idx))
        rounds, tiles = np.asarray(work).tolist()
        safe = jnp.maximum(idx, 0)
        live = idx >= 0
        pool_p = jnp.stack([
            jnp.where(live, jnp.asarray(self._flat_gid)[safe], -1),
            jnp.where(live, jnp.asarray(self._flat_idx)[safe], -1),
            jnp.where(live, jnp.asarray(self._flat_pid)[safe], -1),
        ], axis=-1).astype(jnp.int32)
        pool_d = jnp.where(live, d, jnp.inf)
        if pool_d.shape[1] < m:           # flat DB smaller than the pool
            pad = m - pool_d.shape[1]
            pool_d = jnp.pad(pool_d, ((0, 0), (0, pad)),
                             constant_values=jnp.inf)
            pool_p = jnp.pad(pool_p, ((0, 0), (0, pad), (0, 0)),
                             constant_values=-1)
        stats["sub_s"] += time.perf_counter() - t0
        stats["n_rounds"] = 1
        stats["n_pairs"] = B
        stats["quant_kernel"] = "flat"
        stats["stage1_impl"] = "ref" if use_ref else "pallas"
        stats["flat_rows"] = int(self._flat_n)
        stats["stage1_merge_rounds"] = rounds
        stats["stage1_tiles"] = tiles
        return pool_d, pool_p, {
            "n_cache_hits": 0 if cold else B,
            "n_fetches": self.pool.spec.n_partitions if cold else 0}

    # ------------------------------------------------------------ insert

    def insert(self, vecs: np.ndarray) -> np.ndarray:
        """Dynamic insertion (paper §3.2): route via the cached meta-
        HNSW, append vector+id into the target group's shared overflow
        region through the pool ``append`` verb (one remote WRITE each),
        repack the group when it fills."""
        cfg = self.cfg
        pool = self.pool
        spec = pool.spec
        vecs = np.asarray(vecs, np.float32).reshape(-1, spec.dim)
        with TRACER.span("compute.route", tier="compute", B=int(len(vecs))):
            pids = self._route(jnp.asarray(vecs), b=1)[:, 0]
        gids = np.arange(self._n0 + len(self._extra),
                         self._n0 + len(self._extra) + len(vecs))
        ledger = NetLedger(cfg.fabric)
        for vec, gid, pid in zip(vecs, gids, pids.tolist()):
            self._extra[int(gid)] = vec
            self._extra_pid[int(gid)] = int(pid)
            slot = pool.append(vec, int(gid), int(pid), ledger=ledger)
            if slot < 0:
                group = int(pool.store.meta_table[pid, LA.MT_GROUP])
                ok = pool.repack(group, self._lookup)
                if not ok:
                    # the full rebuild folds _extra — INCLUDING this
                    # vector — into the rebuilt base partitions, so
                    # appending it again would duplicate its gid
                    self._full_rebuild()
                    continue
                self._invalidate_group(group)
                # re-stage through the pool append verb: unlike the old
                # monolithic path (which wrote the host mirror only and
                # left the device twin stale until the next repack), the
                # verb performs the device + quant-mirror twin writes
                slot = pool.append(vec, int(gid), int(pid), ledger=ledger)
                assert slot >= 0, "overflow full right after repack"
                self._flat_synced = False   # repack moved base rows
                continue
            self._invalidate_pid(int(pid))
            if self._flat_synced:
                self._append_flat(int(gid), int(pid))
        self._last_insert_net = ledger.as_dict()
        return gids

    def _append_flat(self, gid: int, pid: int):
        """Keep the dense-resident flat view coherent with one append:
        the writer already holds the row (it produced the WRITE), so
        this is pure compute-side bookkeeping — no wire traffic."""
        n = self._flat_n
        if n >= len(self._flat_idx):
            self._flat_synced = False        # outgrew the pad: resync
            return
        mrow = self.pool.store.meta_table[pid]
        side = int(mrow[LA.MT_SIDE])
        cnt = int(mrow[LA.MT_OV_A if side == 0 else LA.MT_OV_B])
        slot = cnt - 1 if side == 0 else self.pool.spec.ov_cap - cnt
        group = int(mrow[LA.MT_GROUP])
        co = LA.overflow_write_coords(self.pool.spec, group, slot)
        row = (co["vec_block"] * self.pool.spec.slot_vecs
               + co["vec_off"] // self.pool.spec.dim)
        self._flat_idx[n] = row
        self._flat_gid[n] = gid
        self._flat_pid[n] = pid
        self._flat_n = n + 1
        # only row n changed: single-row gather + in-place scatter, so a
        # flat-route insert stays O(D), not O(N*D)
        codes, scales = self.pool.read_quant_rows(
            jnp.asarray([row], jnp.int32))
        self._flat_codes = self._flat_codes.at[n].set(codes[0])
        self._flat_scales = self._flat_scales.at[n].set(scales[0])

    def _invalidate_pid(self, pid: int):
        """Drop stale cached copies (both partners see the ov region)."""
        group = int(self.pool.store.meta_table[pid, LA.MT_GROUP])
        self._invalidate_group(group)

    def _invalidate_group(self, group: int):
        for side in (0, 1):
            p = group * 2 + side
            if self.tiers is not None:
                self.tiers.invalidate(p)    # drops BOTH tiers
            self.cache.drop(p)

    def _full_rebuild(self):
        """np_max exhausted: rebuild the whole region with a larger pad
        (rare; the paper's offline re-pack path)."""
        data = np.concatenate([self._data, np.stack(
            [self._extra[g] for g in sorted(self._extra)])]) \
            if self._extra else self._data
        assigns = np.concatenate([
            self.meta.assignments,
            np.array([self._extra_pid[g] for g in sorted(self._extra)],
                     np.int32)])
        import dataclasses as DC
        self.meta = DC.replace(self.meta, assignments=assigns)
        self._data = data
        self._n0 = data.shape[0]
        self._extra.clear()
        self._extra_pid.clear()
        old_spec = self.pool.spec
        store = LA.build_store(
            data, self.meta, ov_cap=old_spec.ov_cap,
            slot_vecs=old_spec.slot_vecs,
            sub_params=HNSWParams(M=max(self.cfg.sub_M0 // 2, 2),
                                  M0=self.cfg.sub_M0,
                                  ef_construction=self.cfg.ef_construction))
        self.pool.adopt(store)
        if self.tiers is not None:
            self._setup_quant(self._cap0)
        else:
            cap = self.cache.capacity
            self._setup_caches(cap)
        self._flat_synced = False
