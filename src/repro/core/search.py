"""Search on the PAG index (paper §V): graph traversal + Adaptive
Partition Probe early stop (§V-A) + asynchronous partition fetch (Alg 5).

Execution = real computation (exact recall); time = storage-simulator
event clock. This module is the *orchestrator*: the data plane itself is
the staged pipeline in ``repro.dataplane`` —

    plan   (``FetchPlan`` over a ``KeySpace``: probe orders -> keys)
    waves  (``WaveScheduler``: every storage wave, every clock)
    scan   (``ScanStage``: the masked Pallas l2_topk / pq_adc launches)

``search_pag`` builds the plans and sequences the stages; it performs
no storage GETs of its own.

The data plane is batch-coalesced. The graph phase runs for the whole
query batch, then partition probes are coalesced across queries: each
distinct partition is fetched ONCE per batch
(``WaveScheduler.run_coalesced`` — one concurrent RPC wave, hedging
preserved), filled into the optional cache, and scanned for all probing
queries in a single vectorized distance/top-k pass. Per-query latency
accounting survives: each query's ``QueryTimeline`` carries its own
traversal compute and its own probes, with a shared fetch's latency
charged to every prober. Batch throughput (``SearchStats.batch_qps``)
comes from the scheduler's batch-level event clock: fetches issue as
their first prober's traversal retires, coalesced scans amortize the
per-partition dispatch overhead. Results are held to a plain numpy
reference that reads the base array, not the store
(``tests/reference_search.py``).

``SearchConfig`` knobs:

* ``mode`` — ``"async"`` replays Alg 5 (fetches overlap traversal
  compute; scans run as partitions arrive); ``"sync"`` is the blocking
  baseline (all fetches awaited after traversal, scans back-to-back).
  Affects only the simulated clock, never the returned neighbors.
* ``hedge_after_s`` — straggler mitigation: each GET is duplicated
  after this many seconds and the minimum latency wins (also in
  ``get_many``). ``None`` disables hedging.
* ``cache`` — optional ``PartitionCache``. Lookups happen before any
  storage GET; hits cost zero latency for every prober. The cache is
  consulted once per distinct partition and filled from the fetch wave;
  coalesced probers beyond the first are counted as hits (see
  ``PartitionCache.account_shared``).
* ``scan_block`` — candidate-pool block size of the Pallas scan.
* ``replicas`` / ``resilience`` — the fault-tolerance plane. With
  ``replicas=R`` partitions are stored R-way (``write_partitions``)
  and a ``ResiliencePolicy`` (or a long-lived ``ResilientStore``)
  turns each partition fetch into a retry/backoff + timeout + replica
  failover + circuit-breaker chain whose full event-clock cost is
  charged to the query timeline. Per-query damage is reported in
  ``SearchStats.degraded``.
* ``max_inflight`` — bounds the concurrency of the coalesced RPC
  wave (sub-waves on the event clock; queueing charged).
* ``compression`` — ``"pq"`` switches the probe wave to the v2
  compressed payloads: the wave fetches only the per-partition PQ code
  objects, one masked Pallas ADC launch scores every query's pooled
  candidates (``ScanStage.adc_select``), and an exact refine wave
  fetches the full float residual objects only for the partitions
  holding each query's ADC-top ``rerank_k`` candidates. A
  ``PartitionCache`` then caches the *compressed* objects. A lost code
  object degrades exactly like a lost partition; corrupt payloads are
  never admitted to the cache.

Prefetch-ahead (cross-batch pipelining, see ``dataplane.prefetch``):
``prefetch_probes`` hands ``search_pag`` the predicted probe orders of
the NEXT micro-batch; ``search_pag`` issues that wave's payload
objects at the event-clock point where this batch enters its
refine/scan stages and returns the in-flight wave as
``SearchStats.prefetch``. The next call consumes it via ``prefetched``
(key -> (object, residual latency)) and pays only the residual.

v2 payload format (``write_partitions(compression="pq")``), per
partition ``pid`` with ``S`` shards / ``R`` replicas:

* float residuals  ``prefix/{pid%S}/{pid}``            (+ ``/r{j}``)
* PQ codes         ``prefix/{pid%S}/{pid}/pq``         (+ ``/r{j}``)
* codebook         ``prefix/meta/pq_codebook``         (+ ``/r{j}``)

Code objects are colocated with their float siblings (one shard loss
kills both), carry put-time checksums, and replicate round-robin like
the float path. Ids are NOT stored in code objects — the in-memory
``pag.plist`` already maps partition rows to original ids. A residual
object (``KeySpace.pack``) keeps the base's element type: each row is a
4-byte int32 id, bit-cast (``pack_ids``/``unpack_ids``, exact for
billion-scale ids), then the vector. A float32 row is ``[1 + d]``
float32 (the id in column 0); a uint8 / int8 row (BIGANN-style bases)
is ``[4 + d]`` bytes, and such a base stays in its type from the
object to the scan kernel, and in the graph on the device.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.graph_search import greedy_search
from repro.core.pag import PAG
from repro.dataplane.plan import (
    PAYLOAD_CODE,
    PAYLOAD_FLOAT,
    FetchPlan,
    KeySpace,
    probe_orders,
)
from repro.dataplane.prefetch import PrefetchHandle
from repro.dataplane.scan import ID_SENTINEL, INF, ScanStage, dedup_first
from repro.dataplane.wave import WaveScheduler
from repro.obs import get_metrics, get_tracer, host_span
from repro.obs.metrics import COUNT_BUCKETS
from repro.storage.resilience import FetchOutcome, codebook_keys, \
    replica_keys
from repro.storage.simulator import (
    ComputeModel,
    ObjectStore,
    QueryTimeline,
)

__all__ = [
    "ID_SENTINEL", "INF", "DegradedInfo", "SearchConfig", "SearchStats",
    "search_pag", "write_partitions",
]


def write_partitions(pag: PAG, x: np.ndarray, store: ObjectStore,
                     prefix: str = "part", n_shards: int = 1,
                     replicas: int = 1, compression: str = "none",
                     pq_m: int = 8, pq_seed: int = 0):
    """Materialize per-partition residual objects in the storage layer.

    Object = rows of the original id (a BIT-CAST int32, exact for all
    ids — see ``dataplane.plan.pack_ids``) and the vector, in the base's
    element type (``KeySpace.pack``): float32 [cnt, 1 + d] for float
    bases, uint8 / int8 [cnt, 4 + d] for 1-byte integer bases.
    Partitions are round-robined over ``n_shards`` logical
    shards (prefix/<shard>/<pid>) so failure injection can kill a shard
    (fault-tolerance tests). ``replicas=R`` writes R copies per
    partition: the primary under the legacy key and replica j under
    ``prefix/<(pid+j)%n_shards>/<pid>/r<j>`` — adjacent shards, so one
    shard loss never removes every copy (R <= shards).

    ``compression="pq"`` additionally writes the v2 compressed payloads:
    one per-index PQ codebook (trained here, stored under
    ``prefix/meta/pq_codebook``) and per-partition uint8 [cnt, M] code
    objects colocated with their float siblings
    (``prefix/<shard>/<pid>/pq``), replicated and checksummed exactly
    like the float path. Returns the trained ``PQCodebook`` (or None)."""
    if compression not in ("none", "pq"):
        raise ValueError(f"unknown compression: {compression!r}")
    layout = KeySpace(dtype=pag.pg.dtype)
    cb = None
    if compression == "pq":
        from repro.baselines.pq import encode_pq, train_pq
        cb = train_pq(np.asarray(x, np.float32), M=pq_m, seed=pq_seed)
        for key in codebook_keys(prefix, replicas):
            store.put(key, cb.centroids)
    vecs = []
    for pid in range(pag.n_parts):
        cnt = int(pag.pcount[pid])
        ids = pag.plist[pid, :cnt]
        obj = layout.pack(ids, x[ids])
        for key in replica_keys(prefix, pid, n_shards, replicas):
            store.put(key, obj)
        if cb is not None:  # PQ trains and encodes on a float32 view
            vecs.append(np.asarray(x[ids], np.float32))
    if cb is not None:
        # one bulk encode: rows are encoded independently, so each slice
        # equals encoding its partition on its own
        codes_all = encode_pq(cb, np.concatenate(vecs))
        start = 0
        for pid, v in enumerate(vecs):
            codes = codes_all[start:start + len(v)]
            start += len(v)
            for key in replica_keys(prefix, pid, n_shards, replicas,
                                    obj="pq"):
                store.put(key, codes)
    return cb


@dataclasses.dataclass
class SearchConfig:
    L: int = 32                 # traversal beam width
    k: int = 10                 # results
    rho: float = 1.25           # APP scale factor (paper's ρ)
    n_probe_max: int = 16       # cap on fetched partitions
    mode: str = "async"         # async | sync (Alg 5 vs blocking)
    hedge_after_s: Optional[float] = None  # straggler mitigation
    cache: Optional[object] = None  # PartitionCache (beyond-paper, §V-B)
    scan_block: int = 256       # Pallas pool-scan block size
    replicas: int = 1           # R-way partition replication
    # ResiliencePolicy (fresh breaker state per call) or a long-lived
    # ResilientStore wrapping the same store (serving tier: breakers
    # persist across batches). None = the bare skip/raise data plane.
    resilience: Optional[object] = None
    max_inflight: Optional[int] = None  # bound the batched RPC wave
    # Compressed data plane (v2 payloads). "pq": the probe wave fetches
    # only PQ code objects, a masked ADC Pallas launch ranks each
    # query's pooled candidates, and the exact refine wave fetches the
    # float residuals of the partitions holding the ADC-top ``rerank_k``
    # candidates. ``pq_m`` is the write-side subspace count (the search
    # itself reads M from the stored codebook object).
    compression: str = "none"   # none | pq
    pq_m: int = 8
    rerank_k: int = 32          # ADC-top candidates refined exactly


@dataclasses.dataclass
class DegradedInfo:
    """Per-query damage report of the fault-tolerance plane."""
    n_probes_wanted: int = 0    # partitions APP asked for
    n_probes_lost: int = 0      # ... that no replica could serve
    retries: int = 0            # same-replica re-attempts (shared fetch
    failovers: int = 0          # chains charge every prober, like I/O)
    timeouts: int = 0
    corruptions: int = 0
    breaker_skips: int = 0
    breakers_open: int = 0      # open breakers after the fetch phase

    @property
    def degraded(self) -> bool:
        return self.n_probes_lost > 0

    def add_outcome(self, oc: "FetchOutcome"):
        self.retries += oc.retries
        self.failovers += oc.failovers
        self.timeouts += oc.timeouts
        self.corruptions += oc.corruptions
        self.breaker_skips += oc.breaker_skips

    @classmethod
    def merge(cls, infos: Iterable["DegradedInfo"]) -> "DegradedInfo":
        """Batch-level aggregation: sum the per-query damage counters
        (``breakers_open`` is a post-fetch snapshot shared by the whole
        batch, so it takes the max, not the sum). The one place the
        seven fields are summed — callers must not hand-roll this."""
        out = cls()
        for d in infos:
            out.n_probes_wanted += d.n_probes_wanted
            out.n_probes_lost += d.n_probes_lost
            out.retries += d.retries
            out.failovers += d.failovers
            out.timeouts += d.timeouts
            out.corruptions += d.corruptions
            out.breaker_skips += d.breaker_skips
            out.breakers_open = max(out.breakers_open, d.breakers_open)
        return out


@dataclasses.dataclass
class SearchStats:
    latencies_s: List[float]
    n_probes: List[int]
    n_hops: List[int]
    n_distinct_fetches: int = 0   # storage GETs after coalescing + cache
    batch_span_s: float = 0.0     # event-clock makespan of the batch
    degraded: List[DegradedInfo] = dataclasses.field(default_factory=list)
    # PartitionCache health after this batch (cumulative over the
    # cache's lifetime; None when the search ran cache-less)
    cache_hit_rate: Optional[float] = None
    cache_bytes_evicted: int = 0
    # prefetch-ahead pipelining (dataplane.prefetch): probes served from
    # the previous micro-batch's prefetch wave, and the wave this batch
    # issued for the NEXT one (None unless ``prefetch_probes`` was given)
    n_prefetch_hits: int = 0
    prefetch: Optional[PrefetchHandle] = None
    # tracer group of this batch's span tree ("" when not tracing) —
    # lets the frontend attach flow arrows to the per-query tracks
    trace_group: str = ""

    def n_degraded_queries(self) -> int:
        return sum(1 for d in self.degraded if d.degraded)

    def degraded_total(self) -> DegradedInfo:
        """The batch's merged damage report (``DegradedInfo.merge``)."""
        return DegradedInfo.merge(self.degraded)

    def total_retries(self) -> int:
        return self.degraded_total().retries

    def total_failovers(self) -> int:
        return self.degraded_total().failovers

    def qps(self) -> float:
        lat = np.asarray(self.latencies_s)
        return float(1.0 / np.maximum(lat.mean(), 1e-12))

    def batch_qps(self) -> float:
        """Throughput of the whole batch on the simulated event clock."""
        return float(len(self.latencies_s)
                     / max(self.batch_span_s, 1e-12))

    def p999(self) -> float:
        return float(np.quantile(np.asarray(self.latencies_s), 0.999))

    def p99(self) -> float:
        return float(np.quantile(np.asarray(self.latencies_s), 0.99))


def search_pag(pag: PAG, x_dim: int, queries: np.ndarray,
               store: ObjectStore, cfg: SearchConfig,
               compute: Optional[ComputeModel] = None,
               prefix: str = "part", n_shards: int = 1,
               dead_shard_fallback: bool = True,
               prefetched: Optional[Dict[str, Tuple[np.ndarray, float]]]
               = None,
               prefetch_probes: Optional[List[List[int]]] = None,
               trace_t0_s: float = 0.0, pad_rows: int = 0
               ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Returns (result ids [Q, k] original ids, sq-dists [Q, k], stats).

    ``prefetched`` / ``prefetch_probes`` / ``trace_t0_s`` / ``pad_rows``
    serve the micro-batch pipeline (``serving.engine.AnnsFrontend``):
    objects the previous batch already fetched (key -> (object, residual
    latency)), the predicted probe orders of the next batch (their wave
    is issued mid-batch and returned as
    ``stats.prefetch``), the absolute event-clock offset of this batch's
    span tree (so frontend and batch tracks share one clock in the
    trace), and the row count every device launch is padded to (a short
    last micro-batch then reuses the full batch's compiled programs;
    storage, clocks and results see only the real rows)."""
    compute = compute or ComputeModel()
    pg = pag.pg
    q_count = queries.shape[0]
    rows = pad_rows if pad_rows > q_count > 0 else q_count
    # queries in the base's element type, as the graph and the pools
    if pg.dtype != "float32" and queries.dtype != pg.dtype:
        raise ValueError(f"{queries.dtype} queries for an index over "
                         f"{pg.dtype} vectors")
    queries = np.asarray(queries, pg.dtype)
    with host_span("search", queries=q_count, rows=rows):
        with host_span("graph") as sp:
            q_dev = queries
            if rows > q_count:  # pad with copies of a real query
                q_dev = np.concatenate(
                    [q_dev, np.repeat(q_dev[:1], rows - q_count, axis=0)])
            A_dev, nbrs_dev, n_nodes, entry = pg.device_arrays()
            q_dev = jnp.asarray(q_dev)
            sp.set(h2d_bytes=A_dev.nbytes + nbrs_dev.nbytes + q_dev.nbytes)
            res = greedy_search(A_dev, nbrs_dev, n_nodes, entry, q_dev,
                                L=cfg.L, K=cfg.L)
            path_all = np.asarray(res.path)[:q_count]
            path_all_d2 = np.asarray(res.path_dists)[:q_count]
            hops = np.asarray(res.n_hops)[:q_count]
            beam_ids = np.asarray(res.ids)[:q_count]
            beam_d2 = np.asarray(res.dists)[:q_count]
            # the beam merges once a hop: device ms / hops_max is a hop's cost
            sp.set(hops_max=int(hops.max(initial=0)), hops_sum=int(hops.sum()))

        with host_span("search.app_replay") as sp:
            R_edges = pg.nbrs.shape[1]
            traversal_s = [compute.search_hop(int(hops[qi]) * R_edges,
                                              x_dim)
                           for qi in range(q_count)]
            # APP replay: probe order per query (nonempty partitions only)
            probes_all = probe_orders(pag, path_all, path_all_d2, hops,
                                      cfg.rho, cfg.n_probe_max)
            sp.set(probes=sum(map(len, probes_all)))

        if cfg.compression not in ("none", "pq"):
            raise ValueError(f"unknown compression: {cfg.compression!r}")
        pq = cfg.compression == "pq"
        keyspace = KeySpace(prefix, n_shards, cfg.replicas, pg.dtype)

        tracer = get_tracer()
        metrics = get_metrics()
        rec = tracer.enabled   # keep the per-event schedule for the spans
        timelines = [QueryTimeline(record=rec) for _ in range(q_count)]
        degraded = [DegradedInfo(n_probes_wanted=len(probes_all[qi]))
                    for qi in range(q_count)]
        for qi in range(q_count):
            timelines[qi].add_compute(traversal_s[qi])

        sched = WaveScheduler(store, cfg, timelines=timelines,
                              degraded=degraded, compute=compute,
                              dead_shard_fallback=dead_shard_fallback,
                              record=rec, prefetched=prefetched)
        scan = ScanStage(cfg.scan_block, pad_rows=pad_rows)

        codebook, cb_lat = None, 0.0
        if pq:
            with _wave_span("codebook", store):
                codebook, cb_lat, cb_oc = sched.load_codebook(
                    keyspace, cache=cfg.cache)
            if codebook is None:
                # the compressed plane is down for this batch: every probe
                # degrades like a lost partition (beam-only results)
                for qi in range(q_count):
                    degraded[qi].n_probes_lost = len(probes_all[qi])
                    if cb_oc is not None:
                        degraded[qi].add_outcome(cb_oc)
                probes_all = [[] for _ in range(q_count)]
            if cb_lat > 0:  # shared metadata fetch: charged to every query
                for qi in range(q_count):
                    timelines[qi].issue_io(cb_lat, 0.0, label="codebook")

        # probe wave: code objects under "pq" compression, else residuals.
        # The ADC scan of a code object costs scan(cnt, M); exact scans
        # cost scan(cnt, d).
        probe_payload = PAYLOAD_CODE if pq else PAYLOAD_FLOAT
        probe_cost = (lambda o: compute.scan(o.shape[0], o.shape[1])) \
            if pq else (lambda o: compute.scan(o.shape[0], x_dim))
        exact_cost = lambda o: compute.scan(o.shape[0], x_dim)  # noqa: E731
        probe_kind = "adc" if pq else "scan"

        fobjs: Dict[int, np.ndarray] = {}
        refine_all: List[List[int]] = [[] for _ in range(q_count)]

        def finish_batch(t_prefetch: float):
            """The batch's last wave ends here: issue the NEXT
            micro-batch's probe wave (overlapping this batch's refine/scan
            tail on the event clock) and resolve the batch clock.
            Returns (prefetch handle or None, batch makespan)."""
            pf = None
            if prefetch_probes is not None:
                pf = sched.prefetch(prefetch_probes, keyspace,
                                    probe_payload, cache=cfg.cache,
                                    t_issue_s=t_prefetch)
            return pf, sched.finish_batch(cfg.mode)

        plan = _plan(probes_all, keyspace, probe_payload)
        with _wave_span("probe", store):
            wave = sched.run_coalesced(plan, cache=cfg.cache)
            sched.charge_queries(wave, probe_cost, kind=probe_kind)
            # batch event clock: a fetch issues when its FIRST
            # prober's traversal retires; one coalesced scan per
            # distinct partition
            sched.charge_batch_codebook(cb_lat)
            sched.charge_batch_probe(wave, traversal_s, x_dim, pq,
                                     probe_kind)
            if not pq:
                # every traversal retired: the batch's last wave
                handle, batch_span = finish_batch(sched.bt.compute_s)
        objs = wave.objs
        if pq:
            if codebook is not None and objs:
                refine_all = scan.adc_select(codebook, queries,
                                             probes_all, objs, pag,
                                             cfg.rerank_k)
            # stage boundary: the exact refine wave can only issue
            # after the ADC pass over the code objects has retired
            sched.barrier(cfg.mode)
            t_prefetch = sched.bt.compute_s  # refine stage starts here
            fplan = _plan(refine_all, keyspace, PAYLOAD_FLOAT)
            with _wave_span("refine", store):
                fwave = sched.run_coalesced(fplan, cache=None)
                sched.charge_queries(fwave, exact_cost, kind="exact")
                sched.charge_batch_refine(fwave, x_dim)
                handle, batch_span = finish_batch(t_prefetch)
            fobjs = fwave.objs

        if sched.resilient is not None:
            n_open = sched.resilient.n_open_breakers()
            for d in degraded:
                d.breakers_open = n_open

        # candidate pools: aggregation points on the beam (they are
        # dataset points) + residuals of the available probed partitions,
        # deduped by original id (redundant copies, Def 5). Under "pq" the
        # exact pool draws from the refine wave's float objects.
        with host_span("search.pool") as sp:
            pool_src = refine_all if pq else probes_all
            pool_objs = fobjs if pq else objs
            valid_beam = (beam_ids < pg.n_nodes) & (beam_d2 < INF)
            beam_safe = np.minimum(beam_ids, pg.m_cap - 1)
            pool_ids: List[np.ndarray] = []
            pool_vecs: List[np.ndarray] = []
            for qi in range(q_count):
                nodes = beam_safe[qi][valid_beam[qi]]
                ids_list = [pag.node_src[nodes].astype(np.int64)]
                vec_list = [pg.A[nodes].astype(pg.dtype)]
                for pid in pool_src[qi]:
                    obj = pool_objs.get(pid)
                    if obj is None:
                        continue
                    ids, vecs = keyspace.unpack(obj)
                    ids_list.append(ids)
                    vec_list.append(vecs)
                ids_cat = np.concatenate(ids_list)
                keep = dedup_first(ids_cat)
                pool_ids.append(ids_cat[keep])
                pool_vecs.append(np.concatenate(vec_list)[keep])
            sp.set(candidates=sum(map(len, pool_ids)),
                   bytes=sum(v.nbytes for v in pool_vecs))

        out_ids, out_d2 = scan.topk(queries, pool_ids, pool_vecs, cfg.k)

        with host_span("search.stats"):
            stats = SearchStats([], [], [],
                                n_distinct_fetches=sched.n_store,
                                degraded=degraded,
                                n_prefetch_hits=sched.n_prefetch_hits,
                                prefetch=handle)
            if cfg.cache is not None:
                stats.cache_hit_rate = cfg.cache.hit_rate
                stats.cache_bytes_evicted = cfg.cache.bytes_evicted
            for qi in range(q_count):
                tl = timelines[qi]
                lat_q = tl.finish_async() if cfg.mode == "async" \
                    else tl.finish_sync()
                stats.latencies_s.append(lat_q)
                stats.n_probes.append(
                    sum(1 for pid in probes_all[qi] if pid in objs))
                stats.n_hops.append(int(hops[qi]))
            stats.batch_span_s = batch_span
            if metrics.enabled:
                metrics.inc("search.batches")
                metrics.inc("search.queries", q_count)
                for qi in range(q_count):
                    metrics.observe("search.latency_s",
                                    stats.latencies_s[qi])
                    metrics.observe("search.pool_size", len(pool_ids[qi]),
                                    bounds=COUNT_BUCKETS)
                    metrics.observe("search.retries_per_query",
                                    degraded[qi].retries,
                                    bounds=COUNT_BUCKETS)
                if stats.n_prefetch_hits:
                    metrics.inc("search.prefetch_hits",
                                stats.n_prefetch_hits)
                metrics.observe("search.batch_span_s", stats.batch_span_s)
            if rec:
                from repro.obs.trace import emit_search_spans
                stats.trace_group = emit_search_spans(
                    tracer, batch_events=sched.bt.events,
                    batch_span_s=stats.batch_span_s, timelines=timelines,
                    latencies_s=stats.latencies_s, pq=pq,
                    n_probes=stats.n_probes, t0_s=trace_t0_s) or ""
            return out_ids, out_d2, stats


def _plan(probes_all: List[List[int]], keyspace: KeySpace,
          payload: str) -> FetchPlan:
    """``FetchPlan.build`` in its ``anns/plan.build`` span."""
    with host_span("plan.build") as sp:
        plan = FetchPlan.build(probes_all, keyspace, payload)
        sp.set(keys=len(plan.order))
    return plan


@contextlib.contextmanager
def _wave_span(name: str, store: ObjectStore) -> Iterator[None]:
    """``anns/wave.<name>``: a storage wave on the simulated store and
    its clock charges, with the GETs and bytes the store served in it."""
    gets, nbytes = store.n_gets, store.bytes_fetched
    with host_span("wave." + name) as sp:
        yield
        sp.set(gets=store.n_gets - gets, bytes=store.bytes_fetched - nbytes)
