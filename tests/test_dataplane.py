"""Staged data-plane pipeline (repro.dataplane): fetch planning,
probe-order edge cases, the doorkeeper cache-admission gate, and the
prefetch-ahead micro-batch pipeline."""
import numpy as np
import pytest

from repro.core.search import SearchConfig, search_pag, write_partitions
from repro.dataplane import (
    PAYLOAD_CODE,
    PAYLOAD_FLOAT,
    FetchPlan,
    KeySpace,
    PrefetchHandle,
    app_probe_order,
    dedup_first,
    predict_probes,
)
from repro.storage.cache import PartitionCache
from repro.storage.simulator import ObjectStore, StorageConfig


def _fresh_store(built_pag, ds, kind="dfs", seed=7, n_shards=4, **kw):
    store = ObjectStore(StorageConfig.preset(kind, seed=seed))
    write_partitions(built_pag, ds.base, store, n_shards=n_shards, **kw)
    return store


# ------------------------------------------------------------ scan layer

def test_scan_stage_reuses_launch_shapes_across_batches():
    """Pool widths round up to a multiple of scan_block and rows pad to
    pad_rows, so a second batch with other counts compiles nothing."""
    import jax

    from repro.dataplane import ScanStage
    rng = np.random.default_rng(0)
    d, k = 8, 4
    scan = ScanStage(scan_block=128, pad_rows=8)

    def batch(q, lens):
        pools = [rng.standard_normal((n, d)).astype(np.float32)
                 for n in lens]
        ids = [np.arange(n) for n in lens]
        return rng.standard_normal((q, d)).astype(np.float32), ids, pools

    scan.topk(*batch(5, [3, 100, 7, 60, 1]), k)
    built = []
    listener = lambda event, *_, **__: built.append(event)  # noqa: E731
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        queries, ids, pools = batch(3, [120, 9, 40])
        out_ids, out_d2 = scan.topk(queries, ids, pools, k)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert "/jax/core/compile/backend_compile_duration" not in built
    assert out_ids.shape == (3, k) and out_d2.shape == (3, k)
    for qi in range(3):  # the padded launch still answers each row
        want = np.argsort(((pools[qi] - queries[qi]) ** 2).sum(1))[:k]
        assert set(out_ids[qi].tolist()) == set(want.tolist())


# ------------------------------------------------------------ plan layer

def test_keyspace_v2_layout():
    ks = KeySpace("part", n_shards=4, replicas=2)
    assert ks.keys(5) == ["part/1/5", "part/2/5/r1"]
    assert ks.keys(5, PAYLOAD_CODE) == ["part/1/5/pq", "part/2/5/pq/r1"]
    assert ks.codebook_keys() == ["part/meta/pq_codebook",
                                  "part/meta/pq_codebook/r1"]
    with pytest.raises(ValueError):
        ks.keys(5, "bogus")


def test_keyspace_single_replica_is_legacy_keys():
    ks = KeySpace("part", n_shards=4, replicas=1)
    assert ks.keys(7) == ["part/3/7"]
    assert ks.keys(7, PAYLOAD_CODE) == ["part/3/7/pq"]


def test_fetch_plan_coalesces_in_first_probe_order():
    ks = KeySpace("part", n_shards=2)
    plan = FetchPlan.build([[3, 1], [1, 2], []], ks, PAYLOAD_FLOAT)
    assert plan.order == [3, 1, 2]          # distinct, first-probe order
    assert plan.probers == {3: [0], 1: [0, 1], 2: [1]}
    assert plan.first_prober(1) == 0
    assert plan.n_queries == 3
    assert plan.key(3) == "part/1/3"
    assert plan.rkeys(3) == ["part/1/3"]


def test_fetch_plan_empty_batch():
    plan = FetchPlan.build([], KeySpace(), PAYLOAD_FLOAT)
    assert plan.order == [] and plan.probers == {}
    assert plan.n_queries == 0


# ----------------------------------------- probe-order / dedup edge cases

def test_app_probe_order_empty_path():
    radius = np.ones(8, np.float32)
    out = app_probe_order(np.empty(0, np.int64), np.empty(0, np.float32),
                          0, radius, rho=1.25, n_probe_max=16)
    assert out == []


def test_app_probe_order_hops_beyond_path_clamps():
    # a recorded path of 3 hops asked for 10: clamp, don't IndexError
    path = np.array([2, 0, 1], np.int64)
    d2 = np.array([9.0, 4.0, 1.0], np.float32)
    radius = np.full(8, 10.0, np.float32)   # huge radii: no early stop
    out = app_probe_order(path, d2, 10, radius, rho=1.25, n_probe_max=16)
    assert out == [2, 0, 1]


def test_app_probe_order_zero_hops_and_cap():
    path = np.array([2, 0, 1], np.int64)
    d2 = np.array([1.0, 4.0, 9.0], np.float32)
    radius = np.full(8, 10.0, np.float32)
    assert app_probe_order(path, d2, 0, radius, 1.25, 16) == []
    assert app_probe_order(path, d2, 3, radius, 1.25, 2) == [2, 0]


def test_app_probe_order_early_stop_keeps_first_probe():
    # even when the very first node violates the ball rule the order is
    # non-empty (`and probes` guard): the closest partition always probes
    path = np.array([5], np.int64)
    d2 = np.array([100.0], np.float32)
    radius = np.zeros(8, np.float32)
    assert app_probe_order(path, d2, 1, radius, 0.01, 16) == [5]


def test_dedup_first_empty_and_all_duplicates():
    empty = dedup_first(np.empty(0, np.int64))
    assert empty.dtype == bool and empty.shape == (0,)
    allsame = dedup_first(np.full(5, 42, np.int64))
    assert allsame.tolist() == [True, False, False, False, False]
    mixed = dedup_first(np.array([7, 3, 7, 7, 3, 9], np.int64))
    assert mixed.tolist() == [True, True, False, False, False, True]


# ------------------------------------------------------ doorkeeper cache

def _obj(nbytes=400):
    return np.ones(nbytes // 4, np.float32)


def test_admission_policy_validated():
    with pytest.raises(ValueError):
        PartitionCache(1024, admission="lfu")


def test_doorkeeper_admits_on_second_sighting():
    cache = PartitionCache(10_000, admission="doorkeeper")
    cache.get("a")                   # first sighting: vote, miss
    cache.put("a", _obj())
    assert not cache.contains("a")   # one-hit wonder bounced
    assert cache.n_admission_rejects == 1
    cache.get("a")                   # second sighting
    cache.put("a", _obj())
    assert cache.contains("a")       # proven warm -> admitted


def test_doorkeeper_one_hit_wonder_scan_does_not_evict_hot_set():
    # capacity holds exactly the 4-key hot set; any admitted scan key
    # would evict a resident
    hot = [f"hot{i}" for i in range(4)]
    cache = PartitionCache(4 * 400, admission="doorkeeper")
    for key in hot:                  # warm up: 2 sightings each
        cache.get(key)
        cache.put(key, _obj())
        cache.get(key)
        cache.put(key, _obj())
    assert all(cache.contains(k) for k in hot)
    rejects0 = cache.n_admission_rejects
    for i in range(200):             # a long one-hit-wonder scan
        key = f"scan{i}"
        cache.get(key)
        cache.put(key, _obj())
    assert all(cache.contains(k) for k in hot)   # residents survived
    assert cache.n_evictions == 0
    assert cache.n_admission_rejects - rejects0 == 200


def test_always_admission_scan_evicts_hot_set():
    # the contrast case: without the doorkeeper the same scan wipes out
    # the hot working set
    cache = PartitionCache(4 * 400, admission="always")
    for i in range(4):
        cache.put(f"hot{i}", _obj())
    for i in range(200):
        cache.put(f"scan{i}", _obj())
    assert not any(cache.contains(f"hot{i}") for i in range(4))


def test_account_shared_votes_count_for_admission():
    cache = PartitionCache(10_000, admission="doorkeeper")
    cache.account_shared("a", 2)     # 2 coalesced probers = 2 sightings
    cache.put("a", _obj())
    assert cache.contains("a")


def test_contains_is_stats_neutral():
    cache = PartitionCache(10_000, admission="doorkeeper")
    assert not cache.contains("a")
    assert cache.misses == 0 and cache.hits == 0
    cache.put("a", _obj())           # estimate 0 -> bounced, but still
    assert cache.n_admission_rejects == 1
    assert not cache.contains("a")
    assert cache.misses == 0         # no sketch vote, no miss counted


# ----------------------------------------------------- prefetch pipeline

def test_prefetch_handle_residuals():
    arr = np.ones(4, np.float32)
    h = PrefetchHandle(payload=PAYLOAD_CODE, objects={"k": arr},
                       ready_rel_s={"k": 5.0})
    (obj, lat) = h.residuals(3.0)["k"]
    assert obj is arr and lat == pytest.approx(2.0)
    assert h.residuals(7.0)["k"][1] == 0.0   # already landed: free


def test_predict_probes_matches_search(built_pag, small_ds):
    cfg = SearchConfig(L=32, k=10, n_probe_max=16, mode="async")
    q = small_ds.queries[:12]
    predicted = predict_probes(built_pag, q, cfg)
    store = _fresh_store(built_pag, small_ds, kind="mem")
    _, _, st = search_pag(built_pag, small_ds.d, q, store, cfg,
                          n_shards=4)
    # healthy store: every predicted probe is fetched, count for count
    assert st.n_probes == [len(p) for p in predicted]
    assert sum(st.n_probes) > 0


@pytest.mark.parametrize("compression", ["none", "pq"])
def test_prefetch_end_to_end_identical_results(built_pag, small_ds,
                                               compression):
    cfg = SearchConfig(L=32, k=10, n_probe_max=16, mode="async",
                       compression=compression)
    qa = small_ds.queries[:8]        # batch N
    qb = small_ds.queries[8:16]      # batch N+1
    write_kw = dict(compression=compression)

    # baseline: batch N+1 alone, nothing prefetched
    store = _fresh_store(built_pag, small_ds, **write_kw)
    ids0, d20, st0 = search_pag(built_pag, small_ds.d, qb, store, cfg,
                                n_shards=4)

    # pipelined: batch N issues N+1's wave, N+1 consumes the residuals
    store = _fresh_store(built_pag, small_ds, **write_kw)
    probes_b = predict_probes(built_pag, qb, cfg)
    _, _, sta = search_pag(built_pag, small_ds.d, qa, store, cfg,
                           n_shards=4, prefetch_probes=probes_b)
    h = sta.prefetch
    assert h is not None and h.n_keys > 0 and h.objects
    assert h.payload == (PAYLOAD_CODE if compression == "pq"
                         else PAYLOAD_FLOAT)
    assert all(lat >= 0.0 for _, lat in h.residuals(0.0).values())
    ids1, d21, st1 = search_pag(built_pag, small_ds.d, qb, store, cfg,
                                n_shards=4,
                                prefetched=h.residuals(h.issued_rel_s))
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(d20, d21)
    assert st1.n_prefetch_hits > 0
    # prefetched probes skip the storage wave entirely
    assert st1.n_distinct_fetches < st0.n_distinct_fetches


def test_prefetch_without_probes_returns_no_handle(built_pag, small_ds):
    cfg = SearchConfig(L=32, k=10, n_probe_max=16)
    store = _fresh_store(built_pag, small_ds, kind="mem")
    _, _, st = search_pag(built_pag, small_ds.d, small_ds.queries[:4],
                          store, cfg, n_shards=4)
    assert st.prefetch is None and st.n_prefetch_hits == 0


def test_frontend_prefetch_stream_identical(built_pag, small_ds):
    from repro.core.distributed import ShardedServing
    from repro.serving.engine import AnnsFrontend

    cfg = SearchConfig(L=32, k=10, n_probe_max=16, mode="async")
    n_q, chunk = 24, 8
    results = {}
    for prefetch in (False, True):
        store = _fresh_store(built_pag, small_ds)
        serving = ShardedServing(built_pag, store, n_shards=4,
                                 dim=small_ds.d)
        fe = AnnsFrontend(serving, cfg, max_batch=chunk,
                          prefetch=prefetch, auto_flush=False)
        for q in small_ds.queries[:n_q]:
            fe.submit(q)
        fe.flush()
        ids = np.stack([fe.results[t][0] for t in range(n_q)])
        results[prefetch] = (ids, fe.n_prefetch_hits, fe._clock_s)
    np.testing.assert_array_equal(results[False][0], results[True][0])
    assert results[False][1] == 0
    assert results[True][1] > 0
    # hidden latency: the pipelined stream finishes no later
    assert results[True][2] <= results[False][2]


def test_frontend_prefetch_respects_cache(built_pag, small_ds):
    """Prefetch never inflates cache miss counters: resident keys are
    skipped via the stats-neutral ``contains`` probe."""
    from repro.core.distributed import ShardedServing
    from repro.serving.engine import AnnsFrontend

    cache = PartitionCache(1 << 24)
    cfg = SearchConfig(L=32, k=10, n_probe_max=16, mode="async",
                       cache=cache)
    store = _fresh_store(built_pag, small_ds)
    serving = ShardedServing(built_pag, store, n_shards=4,
                             dim=small_ds.d)
    fe = AnnsFrontend(serving, cfg, max_batch=8, prefetch=True,
                      auto_flush=False)
    for q in small_ds.queries[:24]:
        fe.submit(q)
    fe.flush()
    # every lookup is either a real hit or a real miss; prefetch probes
    # of resident keys must not have counted as misses
    assert cache.misses <= sum(len(p) for p in
                               predict_probes(built_pag,
                                              small_ds.queries[:24], cfg))
