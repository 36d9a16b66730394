"""Observability plane: tracer/metrics correctness and — the hard
invariant — ZERO effect on the data plane: search results and
``SearchStats`` must be bit-identical with tracing enabled, disabled,
or never touched (the no-op default)."""
import copy
import json

import numpy as np
import pytest
from reference_search import assert_matches_reference, reference_search

from repro.core.search import (
    DegradedInfo,
    SearchConfig,
    search_pag,
    write_partitions,
)
from repro.obs import get_metrics, get_tracer, observe
from repro.obs.metrics import (
    COUNT_BUCKETS,
    NOOP_METRICS,
    MetricsRegistry,
)
from repro.obs.report import timeline_breakdown
from repro.obs.trace import NOOP_TRACER, Tracer
from repro.storage.simulator import ObjectStore, StorageConfig

PLANES = ("none", "pq")     # SearchConfig.compression: float and PQ


_WRITTEN = {}    # write_partitions kwargs -> a store as written


def _mk_store(built_pag, small_ds, **kw):
    """A fresh store as ``write_partitions`` leaves it (seeded, no call
    drawn yet). Each layout is written once (PQ training is the slow
    part) and deep-copied per call."""
    key = tuple(sorted(kw.items()))
    if key not in _WRITTEN:
        store = ObjectStore(StorageConfig.preset("dfs", seed=1))
        write_partitions(built_pag, small_ds.base, store, n_shards=4, **kw)
        _WRITTEN[key] = store
    return copy.deepcopy(_WRITTEN[key])


def _search(built_pag, small_ds, store, **cfg_kw):
    cfg = SearchConfig(L=32, k=10, n_probe_max=16, **cfg_kw)
    return search_pag(built_pag, small_ds.d, small_ds.queries[:16],
                      store, cfg, n_shards=4)


# ---------------------------------------------------------------- identity

@pytest.mark.parametrize("compression", PLANES)
def test_tracing_disabled_is_bit_identical(built_pag, small_ds,
                                           compression):
    # fresh identically-seeded store per run: the simulator's latency
    # jitter RNG advances per call, so a shared store would differ
    # between runs regardless of tracing
    ids0, d20, st0 = _search(built_pag, small_ds,
                             _mk_store(built_pag, small_ds,
                                       compression=compression),
                             compression=compression)
    with observe(tracer=Tracer(), metrics=MetricsRegistry()):
        ids1, d21, st1 = _search(built_pag, small_ds,
                                 _mk_store(built_pag, small_ds,
                                           compression=compression),
                                 compression=compression)
    ids2, d22, st2 = _search(built_pag, small_ds,
                             _mk_store(built_pag, small_ds,
                                       compression=compression),
                             compression=compression)
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(d20, d21)
    np.testing.assert_array_equal(ids0, ids2)
    assert st0.latencies_s == st1.latencies_s == st2.latencies_s
    assert st0.batch_span_s == st1.batch_span_s == st2.batch_span_s
    assert st0.n_probes == st1.n_probes
    assert st0.n_distinct_fetches == st1.n_distinct_fetches


@pytest.mark.parametrize("compression", PLANES)
def test_root_span_matches_stats(built_pag, small_ds, compression):
    """Tracer root spans ARE the stats: the batch root's duration equals
    ``batch_span_s`` and each query root equals its latency."""
    store = _mk_store(built_pag, small_ds, compression=compression)
    tr = Tracer()
    with observe(tracer=tr):
        _, _, st = _search(built_pag, small_ds, store,
                           compression=compression)
    (root,) = tr.roots("batch")
    assert root.dur_s == pytest.approx(st.batch_span_s, abs=1e-12)
    qroots = tr.roots("query")
    assert len(qroots) == len(st.latencies_s)
    for s, lat in zip(qroots, st.latencies_s):
        assert s.dur_s == pytest.approx(lat, abs=1e-12)


@pytest.mark.parametrize("compression", PLANES)
def test_child_spans_contained_in_parent(built_pag, small_ds, compression):
    store = _mk_store(built_pag, small_ds, compression=compression)
    tr = Tracer()
    with observe(tracer=tr):
        _search(built_pag, small_ds, store, compression=compression)
    for root in tr.roots("batch") + tr.roots("query"):
        kids = [s for s in tr.spans
                if s.track == root.track and s is not root]
        assert kids, f"no children under {root.track}"
        for s in kids:
            assert s.t0_s >= root.t0_s - 1e-12
            assert s.t1_s <= root.t1_s + 1e-9
        # the compute-thread slices ("X") tile the root: sum <= parent
        tiled = sum(s.dur_s for s in kids if s.ph == "X")
        assert tiled <= root.dur_s + 1e-9


def test_trace_root_matches_stats_and_reference(built_pag, small_ds):
    """A traced batch: its root span equals the stats' batch span and
    names its plane, and the traced answers are the plain reference's."""
    store = _mk_store(built_pag, small_ds)
    tr = Tracer()
    with observe(tracer=tr):
        ids, d2, st = _search(built_pag, small_ds, store)
    (root,) = tr.roots("batch")
    assert root.dur_s == pytest.approx(st.batch_span_s, abs=1e-12)
    assert root.args == {"pq": False, "queries": 16}
    q = small_ds.queries[:16]
    ref = reference_search(built_pag, small_ds.base, q,
                           SearchConfig(L=32, k=10, n_probe_max=16))
    assert_matches_reference(ids, d2, ref, small_ds.base, q)


# ------------------------------------------------------------------- trace

def test_trace_json_is_perfetto_loadable(built_pag, small_ds, tmp_path):
    store = _mk_store(built_pag, small_ds)
    tr = Tracer()
    with observe(tracer=tr):
        _search(built_pag, small_ds, store)
    path = tr.save(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert evs, "empty trace"
    phases = {e["ph"] for e in evs}
    assert "X" in phases and "M" in phases
    for e in evs:
        assert {"ph", "pid", "name"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
    # async b/e pairs balance per id
    b = [e["id"] for e in evs if e["ph"] == "b"]
    e_ = [e["id"] for e in evs if e["ph"] == "e"]
    assert sorted(b) == sorted(e_)
    # the two clock domains are separate perfetto processes
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {"event-clock", "host-wall"}
    # the host-wall group holds the search's host spans at their real
    # starts: nested as they ran, the stages starting in pipeline order
    wall = [s for s in tr.spans if s.group == "host-wall"]
    by_name = {s.name: s for s in wall}
    outer, launch = by_name["anns/search"], by_name["anns/scan.topk_launch"]
    assert outer.t0_s <= launch.t0_s and launch.t1_s <= outer.t1_s
    stages = ["anns/search", "anns/graph", "anns/search.app_replay",
              "anns/plan.build", "anns/wave.probe", "anns/search.pool",
              "anns/scan.topk_pad", "anns/scan.topk_launch",
              "anns/search.stats"]
    starts = [by_name[n].t0_s for n in stages]
    assert starts == sorted(starts) and starts[0] >= 0.0
    for s in wall:
        assert outer.t0_s <= s.t0_s and s.t1_s <= outer.t1_s


def test_host_span_goes_to_an_installed_tracer_only():
    from repro.obs import host_span
    with host_span("probe", keys=3) as sp:   # no tracer: profiler only
        sp.set(gets=2)
    assert sp.stats == {"keys": 3, "gets": 2}
    tr = Tracer()
    with observe(tracer=tr):
        with host_span("outer"):
            with host_span("inner", h2d_bytes=4096) as sp:
                sp.set(batch=1)
    inner, outer = tr.spans               # recorded as each one closes
    assert (inner.name, outer.name) == ("anns/inner", "anns/outer")
    assert inner.group == outer.group == "host-wall"
    assert inner.args == {"h2d_bytes": 4096, "batch": 1}
    assert 0.0 <= outer.t0_s <= inner.t0_s
    assert inner.t1_s <= outer.t1_s
    assert not NOOP_TRACER.spans


@pytest.mark.parametrize("compression", ["none", "pq"])
def test_profiler_session_leaves_results_identical(built_pag, small_ds,
                                                   tmp_path, compression):
    """The host spans are always emitted; an active profiler session
    records them and changes no id, distance or modelled latency."""
    import jax
    kw = {"compression": compression}
    ids0, d20, st0 = _search(built_pag, small_ds,
                             _mk_store(built_pag, small_ds, **kw), **kw)
    with jax.profiler.trace(str(tmp_path)):
        ids1, d21, st1 = _search(built_pag, small_ds,
                                 _mk_store(built_pag, small_ds, **kw), **kw)
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(d20, d21)
    assert st0.latencies_s == st1.latencies_s
    assert list(tmp_path.rglob("*.xplane.pb"))


def test_pq_trace_has_stage_spans(built_pag, small_ds):
    tr = Tracer()
    with observe(tracer=tr):
        ids0, _, st0 = _search(built_pag, small_ds,
                               _mk_store(built_pag, small_ds,
                                         compression="pq"),
                               compression="pq", rerank_k=32)
    stages = {s.name for s in tr.spans if s.cat == "stage"}
    assert {"fetch_wave", "adc_scan", "refine_wave",
            "refine_scan"} <= stages
    # and the compressed plane is also identity-safe under tracing
    # (fresh store: the latency-jitter RNG advances per call)
    ids1, _, st1 = _search(built_pag, small_ds,
                           _mk_store(built_pag, small_ds,
                                     compression="pq"),
                           compression="pq", rerank_k=32)
    np.testing.assert_array_equal(ids0, ids1)
    assert st0.latencies_s == st1.latencies_s


def test_timeline_breakdown_renders(built_pag, small_ds):
    store = _mk_store(built_pag, small_ds)
    tr = Tracer()
    with observe(tracer=tr):
        _search(built_pag, small_ds, store)
    text = timeline_breakdown(tr)
    assert "traversal" in text and "fetch stall" in text
    assert "%" in text
    assert timeline_breakdown(Tracer()) == "(no batch spans recorded)"


def test_tracer_caps_drop_not_crash(built_pag, small_ds):
    tr = Tracer(max_tracks=2, max_spans=50)
    with observe(tracer=tr):
        store = _mk_store(built_pag, small_ds)
        _search(built_pag, small_ds, store)
    assert len(tr.spans) <= 50
    assert tr.n_dropped > 0
    tr.save("/dev/null")  # still exports


def test_noop_singletons_are_default():
    assert get_tracer() is NOOP_TRACER
    assert get_metrics() is NOOP_METRICS
    with observe(tracer=Tracer(), metrics=MetricsRegistry()):
        assert get_tracer().enabled and get_metrics().enabled
    assert get_tracer() is NOOP_TRACER
    assert get_metrics() is NOOP_METRICS


# ----------------------------------------------------------------- metrics

def test_metrics_snapshot(built_pag, small_ds):
    store = _mk_store(built_pag, small_ds)
    mx = MetricsRegistry()
    with observe(metrics=mx):
        _, _, st = _search(built_pag, small_ds, store)
    snap = mx.snapshot()
    assert snap["search.batches"] == 1.0
    assert snap["search.queries"] == 16.0
    assert snap["storage.gets"] >= st.n_distinct_fetches
    assert snap["search.latency_s.count"] == 16.0
    assert snap["search.latency_s.mean"] == pytest.approx(
        float(np.mean(st.latencies_s)))
    # histogram cumulative buckets are monotone in the bound
    les = sorted((float(k.rsplit("_", 1)[1]), v)
                 for k, v in snap.items()
                 if k.startswith("search.latency_s.le_"))
    counts = [v for _, v in les]
    assert counts == sorted(counts)
    assert counts[-1] <= snap["search.latency_s.count"]
    mx.reset()
    assert mx.snapshot() == {}


def test_histogram_quantiles_and_bounds():
    from repro.obs.metrics import Histogram
    h = Histogram(bounds=COUNT_BUCKETS)
    for v in (0, 1, 1, 3, 300):
        h.observe(v)
    assert h.count == 5 and h.max == 300
    assert h.quantile(0.5) == 1.0
    assert h.quantile(1.0) == 300  # overflow bucket reports max
    assert Histogram().quantile(0.9) == 0.0


def test_breaker_transition_metrics():
    from repro.storage.resilience import CircuitBreaker
    mx = MetricsRegistry()
    with observe(metrics=mx):
        br = CircuitBreaker(fail_threshold=2, cooldown_requests=1)
        br.record_failure()
        br.record_failure()          # -> open
        assert not br.allow()        # cooldown tick
        assert br.allow()            # -> half_open probe
        br.record_success()          # -> closed
    snap = mx.snapshot()
    assert snap["breaker.to_open"] == 1.0
    assert snap["breaker.to_half_open"] == 1.0
    assert snap["breaker.to_closed"] == 1.0


# -------------------------------------------------------------- satellites

def test_cache_hit_rate_zero_lookups_and_reset():
    from repro.storage.cache import PartitionCache
    c = PartitionCache(1 << 20)
    assert c.hit_rate == 0.0                    # no NaN on zero lookups
    c.put("a", np.zeros(8, np.float32))
    assert c.get("a") is not None and c.get("b") is None
    assert c.hit_rate == pytest.approx(0.5)
    c.reset_stats()
    assert c.hits == c.misses == 0 and c.hit_rate == 0.0
    assert c.get("a") is not None               # objects survive reset
    assert c.hit_rate == 1.0


def test_degraded_info_merge():
    a = DegradedInfo(n_probes_wanted=4, n_probes_lost=1, retries=2,
                     failovers=1, timeouts=1, corruptions=0,
                     breaker_skips=3, breakers_open=1)
    b = DegradedInfo(n_probes_wanted=2, n_probes_lost=0, retries=1,
                     failovers=0, timeouts=0, corruptions=2,
                     breaker_skips=0, breakers_open=2)
    m = DegradedInfo.merge([a, b])
    assert (m.n_probes_wanted, m.n_probes_lost) == (6, 1)
    assert (m.retries, m.failovers, m.timeouts) == (3, 1, 1)
    assert (m.corruptions, m.breaker_skips) == (2, 3)
    assert m.breakers_open == 2                 # max, not sum
    assert DegradedInfo.merge([]).retries == 0


def test_frontend_queue_wait_and_spans(built_pag, small_ds):
    from repro.core.distributed import ShardedServing
    from repro.serving.engine import AnnsFrontend
    store = _mk_store(built_pag, small_ds)
    srv = ShardedServing(pag=built_pag, store=store, n_shards=4,
                         dim=small_ds.d)
    cfg = SearchConfig(L=32, k=10, n_probe_max=16)
    tr, mx = Tracer(), MetricsRegistry()
    with observe(tracer=tr, metrics=mx):
        fe = AnnsFrontend(srv, cfg, max_batch=8)
        tickets = [fe.submit(q) for q in small_ds.queries[:6]]
        fe.flush()
    for t in tickets:
        assert t in fe.results
    flushes = [s for s in tr.spans if s.cat == "flush"]
    assert len(flushes) == 1
    assert flushes[0].dur_s == pytest.approx(
        fe.last_stats.batch_span_s)
    ticket_spans = [s for s in tr.spans if s.cat == "ticket"]
    assert len(ticket_spans) == 6
    assert all(s.args["queue_wait_s"] >= 0.0 for s in ticket_spans)
    # the flush's host span carries the chunk's summed queue wait
    (flush,) = [s for s in tr.spans if s.name == "anns/frontend.flush"]
    assert flush.args["tickets"] == 6 and flush.args["first_ticket"] == 0
    assert flush.args["queue_wait_ns_sum"] == pytest.approx(
        sum(s.args["queue_wait_s"] for s in ticket_spans) * 1e9, abs=1.0)
    snap = mx.snapshot()
    assert snap["frontend.flushes"] == 1.0
    assert snap["frontend.batch_size.count"] == 1.0
    assert snap["frontend.queue_wait_s.count"] == 6.0
    summary = fe.degraded_summary()
    assert summary is None or isinstance(summary, DegradedInfo)


def test_bench_json_roundtrip(tmp_path):
    from benchmarks.common import (
        BENCH_SCHEMA_VERSION,
        collect_rows,
        emit,
        emit_bench_json,
    )
    with collect_rows() as rows:
        emit("m/a", 12.5, "recall=0.9;qps=100;tag=fast;flagged")
    path = emit_bench_json("unit", rows, out_dir=str(tmp_path))
    doc = json.loads(open(path).read())
    assert doc["schema_version"] == BENCH_SCHEMA_VERSION
    assert doc["mode"] == "unit"
    (row,) = doc["rows"]
    assert row["name"] == "m/a" and row["us_per_call"] == 12.5
    assert row["derived"] == {"recall": 0.9, "qps": 100.0,
                              "tag": "fast", "flagged": True}
    # emit() outside a collector must not leak into old lists
    emit("m/b", 1.0, "x=1")
    assert len(rows) == 1


def test_flow_events_balanced_and_capped():
    tr = Tracer()
    tr.span("a", "root", 0.0, 1.0)
    tr.flow("a", 0.0, "b", 0.5)
    doc = tr.to_chrome()
    starts = [e for e in doc["traceEvents"] if e.get("ph") == "s"]
    ends = [e for e in doc["traceEvents"] if e.get("ph") == "f"]
    assert len(starts) == len(ends) == 1
    assert starts[0]["id"] == ends[0]["id"]
    assert ends[0]["bp"] == "e"            # bind to enclosing slice
    # over the span budget the WHOLE flow is dropped: ids stay balanced
    tight = Tracer(max_spans=1)
    tight.flow("a", 0.0, "b", 0.5)
    assert tight.spans == [] and tight.n_dropped == 1
    # over the track cap likewise
    capped = Tracer(max_tracks=1)
    capped.track("a")
    capped.flow("a", 0.0, "b", 0.5)
    assert capped.spans == []


def test_frontend_flow_arrows_balanced(built_pag, small_ds):
    """Every flushed ticket gets one flow arrow to its per-query track;
    the exported Chrome JSON always has balanced "s"/"f" id pairs."""
    from repro.core.distributed import ShardedServing
    from repro.serving.engine import AnnsFrontend
    store = _mk_store(built_pag, small_ds)
    srv = ShardedServing(pag=built_pag, store=store, n_shards=4,
                         dim=small_ds.d)
    cfg = SearchConfig(L=32, k=10, n_probe_max=16)
    tr = Tracer()
    with observe(tracer=tr):
        fe = AnnsFrontend(srv, cfg, max_batch=8)
        for q in small_ds.queries[:6]:
            fe.submit(q)
        fe.flush()
    doc = tr.to_chrome()
    s_ids = sorted(e["id"] for e in doc["traceEvents"]
                   if e.get("ph") == "s")
    f_ids = sorted(e["id"] for e in doc["traceEvents"]
                   if e.get("ph") == "f")
    assert len(s_ids) == 6                  # one arrow per ticket
    assert s_ids == f_ids                   # balanced, matching ids
    assert len(set(s_ids)) == 6             # distinct arrows
    # arrows start on the frontend track and land on a query track
    flows = [s for s in tr.spans if s.ph == "s"]
    assert all(s.track == "frontend" for s in flows)
    lands = [s.track for s in tr.spans if s.ph == "f"]
    assert all("/q" in t for t in lands)


def _parse_openmetrics(text: str):
    """Tiny OpenMetrics text parser: returns (types, samples) where
    samples maps "name" or ("name", le) -> float."""
    types, samples = {}, {}
    lines = text.splitlines()
    assert lines[-1] == "# EOF"
    for line in lines[:-1]:
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            types[name] = kind
            continue
        assert not line.startswith("#")
        name, val = line.rsplit(" ", 1)
        if "{" in name:
            base, label = name[:-1].split("{")
            assert label.startswith('le="')
            samples[(base, label[4:-1])] = float(val)
        else:
            samples[name] = float(val)
    return types, samples


def test_openmetrics_roundtrip():
    mx = MetricsRegistry()
    mx.inc("storage.gets", 3)
    mx.inc("search.prefetch_hits", 12345678901234)  # big int: exact
    mx.set_gauge("cache.hit_rate", 0.7071067811865476)
    for v in (0.0, 1.0, 1.5, 300.0):
        mx.observe("frontend.batch-size", v, bounds=COUNT_BUCKETS)
    text = mx.to_openmetrics()
    assert text.endswith("# EOF\n")
    types, samples = _parse_openmetrics(text)
    snap = mx.snapshot()

    assert types["storage_gets"] == "counter"
    assert samples["storage_gets_total"] == snap["storage.gets"]
    assert samples["search_prefetch_hits_total"] == 12345678901234
    assert types["cache_hit_rate"] == "gauge"
    # repr round-trips full float precision (no %g truncation)
    assert samples["cache_hit_rate"] == snap["cache.hit_rate"]

    h = "frontend_batch_size"                  # dots AND dashes mapped
    assert types[h] == "histogram"
    assert samples[f"{h}_count"] == snap["frontend.batch-size.count"]
    assert samples[f"{h}_sum"] == snap["frontend.batch-size.sum"]
    assert samples[(f"{h}_bucket", "+Inf")] == 4
    # cumulative buckets match the snapshot's .le_* series bound for
    # bound and are monotone
    acc = []
    for b in COUNT_BUCKETS:
        v = samples[(f"{h}_bucket", f"{b:g}")]
        assert v == snap[f"frontend.batch-size.le_{b:g}"]
        acc.append(v)
    assert acc == sorted(acc)


def test_graph_span_counts_the_hops(built_pag, small_ds):
    """``anns/graph`` carries the hops of the batch's slowest query and
    their sum, as ``greedy_search`` reports them for the real rows."""
    import jax.numpy as jnp

    from repro.core.graph_search import greedy_search
    tr = Tracer()
    with observe(tracer=tr):
        _search(built_pag, small_ds, _mk_store(built_pag, small_ds))
    (graph,) = [s for s in tr.spans if s.name == "anns/graph"]
    A, nbrs, n_nodes, entry = built_pag.pg.device_arrays()
    hops = np.asarray(greedy_search(
        A, nbrs, n_nodes, entry, jnp.asarray(small_ds.queries[:16]),
        L=32, K=32).n_hops)
    assert graph.args["hops_max"] == int(hops.max()) > 1
    assert graph.args["hops_sum"] == int(hops.sum())
