"""Batched data plane: answers against the plain reference, coalesced
fetch accounting, k-larger-than-pool / all-shards-dead edge cases, and
the micro-batching serving front-end."""
import numpy as np
import pytest
from reference_search import assert_matches_reference, reference_search

from repro.core.search import (
    ID_SENTINEL,
    INF,
    SearchConfig,
    search_pag,
    write_partitions,
)
from repro.dataplane.scan import dedup_first
from repro.storage.simulator import ObjectStore, StorageConfig


def _fresh_store(built_pag, ds, kind="dfs", seed=7, n_shards=4):
    store = ObjectStore(StorageConfig.preset(kind, seed=seed))
    write_partitions(built_pag, ds.base, store, n_shards=n_shards)
    return store


# ------------------------------------------------------------------ reference

def test_search_matches_reference(built_pag, small_ds):
    """The coalesced plane returns the exact top-k of the beam plus the
    probed partitions, as the plain reference reads them from the base
    array; every probe is served, and each query ran its own hops."""
    cfg = SearchConfig(L=64, k=10, n_probe_max=32, mode="async")
    q = small_ds.queries
    ids, d2, st = search_pag(built_pag, small_ds.d, q,
                             _fresh_store(built_pag, small_ds), cfg,
                             n_shards=4)
    ref = reference_search(built_pag, small_ds.base, q, cfg)
    assert_matches_reference(ids, d2, ref, small_ds.base, q)
    assert all(d.n_probes_lost == 0 for d in st.degraded)
    assert st.n_probes == [d.n_probes_wanted for d in st.degraded]
    assert len(st.n_hops) == len(q) and min(st.n_hops) > 0


def test_batched_dedups_fetches(built_pag, small_ds):
    """Cross-query coalescing: distinct storage fetches <= sum of
    per-query probes, and the store sees exactly that many GETs."""
    store = _fresh_store(built_pag, small_ds)
    cfg = SearchConfig(L=64, k=10, n_probe_max=32)
    before = store.n_gets
    _, _, st = search_pag(built_pag, small_ds.d, small_ds.queries, store,
                          cfg, n_shards=4)
    assert st.n_distinct_fetches <= sum(st.n_probes)
    assert st.n_distinct_fetches < sum(st.n_probes)  # real overlap
    assert store.n_gets - before == st.n_distinct_fetches
    assert store.n_batch_gets == 1  # one coalesced wave per batch


def test_batched_throughput_wins(built_pag, small_ds):
    """A coalesced batch's simulated throughput beats a serial stream of
    the same queries sent one at a time (no cross-query coalescing) by
    >= 3x on DFS-tier storage."""
    cfg = SearchConfig(L=64, k=10, n_probe_max=32, mode="async")
    q = small_ds.queries[:32]
    _, _, st_b = search_pag(built_pag, small_ds.d, q,
                            _fresh_store(built_pag, small_ds), cfg,
                            n_shards=4)
    store = _fresh_store(built_pag, small_ds)
    serial_s = sum(search_pag(built_pag, small_ds.d, q[i:i + 1], store, cfg,
                              n_shards=4)[2].batch_span_s
                   for i in range(len(q)))
    serial_qps = len(q) / serial_s
    assert st_b.batch_qps() > 3 * serial_qps, (st_b.batch_qps(),
                                               serial_qps)


# ------------------------------------------------------------------ edge cases

def test_k_larger_than_candidate_pool(small_ds):
    """k beyond the whole candidate pool: rows pad with -1 ids and INF
    distances instead of raising or recycling candidates."""
    from repro.core.pag import build_pag

    tiny = small_ds.base[:120]
    pag = build_pag(tiny, p=0.1, k=4, lam=3.0, redundancy=2, seed=0)
    store = ObjectStore(StorageConfig.preset("mem"))
    write_partitions(pag, tiny, store, n_shards=2)
    cfg = SearchConfig(L=8, k=200, n_probe_max=2)  # k >> pool
    ids, d2, _ = search_pag(pag, tiny.shape[1], small_ds.queries[:5],
                            store, cfg, n_shards=2)
    assert ids.shape == (5, 200) and d2.shape == (5, 200)
    assert (ids == -1).any(axis=1).all()       # every row is partial
    assert np.all(d2[ids == -1] >= INF)        # padding distance
    valid = ids >= 0
    assert np.all(ids[valid] < len(tiny))
    # padding is suffix-shaped: no valid id after the first -1
    for row in ids:
        first_pad = np.argmax(row == -1)
        assert (row[first_pad:] == -1).all()


def test_all_shards_dead_degraded(built_pag, small_ds):
    """dead_shard_fallback=True with every shard down must return padded
    beam-only results, not raise."""
    store = _fresh_store(built_pag, small_ds, kind="mem")
    store.kill_prefix("part/")
    cfg = SearchConfig(L=64, k=10, n_probe_max=32)
    ids, d2, st = search_pag(built_pag, small_ds.d, small_ds.queries,
                             store, cfg, n_shards=4,
                             dead_shard_fallback=True)
    assert (np.asarray(st.n_probes) == 0).all()
    assert (ids >= -1).all()
    assert (ids[:, 0] >= 0).all()  # beam still yields candidates
    assert st.n_distinct_fetches == 0


def test_dead_shard_raises_without_fallback(built_pag, small_ds):
    store = _fresh_store(built_pag, small_ds, kind="mem")
    store.kill_prefix("part/0/")
    cfg = SearchConfig(L=64, k=10, n_probe_max=32)
    with pytest.raises(KeyError):
        search_pag(built_pag, small_ds.d, small_ds.queries, store, cfg,
                   n_shards=4, dead_shard_fallback=False)


def test_dedup_sentinel():
    """Invalid ids (< 0) map to the 2**62 sentinel and are dropped;
    duplicates keep their first occurrence only."""
    ids = np.array([7, -1, 3, 7, 3, 12, -1], np.int64)
    keep = dedup_first(ids)
    assert keep.tolist() == [True, False, True, False, False, True, False]
    assert ID_SENTINEL == 2 ** 62
    assert (dedup_first(np.array([-1, -1], np.int64)) == False).all()  # noqa: E712


# ------------------------------------------------------- latency accounting

def test_get_many_matches_sequential_gets():
    """get_many is one concurrent wave of the same per-key draws: same
    seed => identical latencies to sequential gets, one n_batch_gets."""
    cfg = StorageConfig.preset("dfs", seed=11)
    s1, s2 = ObjectStore(cfg), ObjectStore(cfg)
    for s in (s1, s2):
        for i in range(6):
            s.put(f"p/{i}", np.full((16, 4), i, np.float32))
    keys = [f"p/{i}" for i in range(6)]
    batched = s1.get_many(keys)
    seq = {k: s2.get(k) for k in keys}
    for k in keys:
        assert batched[k][1] == seq[k][1]
        assert np.array_equal(batched[k][0], seq[k][0])
    assert s1.n_gets == s2.n_gets == len(keys)
    assert s1.n_batch_gets == 1 and s2.n_batch_gets == 0


def test_get_many_hedging_and_missing():
    cfg = StorageConfig.preset("dfs", seed=3)
    s_plain, s_hedge = ObjectStore(cfg), ObjectStore(cfg)
    for s in (s_plain, s_hedge):
        for i in range(200):
            s.put(f"p/{i}", np.zeros(64, np.float32))
    keys = [f"p/{i}" for i in range(200)]
    lat_p = np.array([v[1] for v in s_plain.get_many(keys).values()])
    hedge = float(np.quantile(lat_p, 0.9))
    lat_h = np.array([v[1] for v in
                      s_hedge.get_many(keys, hedge_after_s=hedge).values()])
    # hedging can only cap a draw that exceeded the hedge timeout
    assert lat_h.max() <= lat_p.max()
    assert np.quantile(lat_h, 0.99) <= np.quantile(lat_p, 0.99) + 1e-12

    s_plain.kill_prefix("p/1")
    with pytest.raises(KeyError):
        s_plain.get_many(["p/1", "p/2"], on_missing="raise")
    out = s_plain.get_many(["p/1", "p/2"], on_missing="skip")
    assert "p/1" not in out and "p/2" in out
    with pytest.raises(ValueError):
        s_plain.get_many(["p/2"], on_missing="bogus")


def test_get_hedged_matches_min_semantics():
    """get_hedged = min(first draw, hedge + duplicate draw); an infinite
    hedge timeout reduces to the plain get."""
    cfg = StorageConfig.preset("dfs", seed=5)
    s1, s2 = ObjectStore(cfg), ObjectStore(cfg)
    s1.put("a", np.zeros(32, np.float32))
    s2.put("a", np.zeros(32, np.float32))
    for _ in range(500):
        lat_plain = s1.get("a")[1]
        lat_hedge = s2.get_hedged("a", hedge_after_s=1e9)[1]
        assert lat_hedge == lat_plain  # same rng stream, never hedges
    # a tiny timeout always issues the duplicate: lat <= timeout + draw
    s3 = ObjectStore(cfg)
    s3.put("a", np.zeros(32, np.float32))
    for _ in range(200):
        assert s3.get_hedged("a", hedge_after_s=0.0)[1] >= 0.0


def test_shared_fetch_charged_to_every_prober(built_pag, small_ds):
    """Repeat the same query: the coalesced wave fetches its partitions
    once but charges both probers, so both rows see identical nonzero
    probe counts and (same-draw) latencies."""
    q = np.repeat(small_ds.queries[:1], 2, axis=0)
    store = _fresh_store(built_pag, small_ds)
    cfg = SearchConfig(L=64, k=10, n_probe_max=32, mode="async")
    ids, _, st = search_pag(built_pag, small_ds.d, q, store, cfg,
                            n_shards=4)
    assert np.array_equal(ids[0], ids[1])
    assert st.n_probes[0] == st.n_probes[1] > 0
    assert st.n_distinct_fetches == st.n_probes[0]  # coalesced, not 2x
    assert st.latencies_s[0] == pytest.approx(st.latencies_s[1])


# ------------------------------------------------------------------- serving

def test_anns_frontend_micro_batching(built_pag, small_ds):
    """Individually-submitted queries flushed as one batch match the
    direct batched search and share the coalesced fetch wave."""
    from repro.core.distributed import ShardedServing
    from repro.serving.engine import AnnsFrontend

    store = _fresh_store(built_pag, small_ds, kind="mem")
    srv = ShardedServing(pag=built_pag, store=store, n_shards=4,
                         dim=small_ds.d)
    cfg = SearchConfig(L=64, k=10, n_probe_max=32)
    direct_ids, direct_d2, _ = srv.search(small_ds.queries[:16], cfg)

    fe = AnnsFrontend(srv, cfg, max_batch=64)
    tickets = [fe.submit(small_ds.queries[i]) for i in range(16)]
    results = fe.flush()
    assert store.n_batch_gets >= 1
    for row, t in enumerate(tickets):
        ids_t, d2_t, lat_t = results[t]
        assert np.array_equal(ids_t, direct_ids[row])
        assert np.array_equal(d2_t, direct_d2[row])
        assert lat_t > 0

    # auto-flush at max_batch
    fe2 = AnnsFrontend(srv, cfg, max_batch=4)
    for i in range(4):
        fe2.submit(small_ds.queries[i])
    assert len(fe2.results) == 4  # flushed without an explicit call
