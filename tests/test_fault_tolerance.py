"""Fault tolerance: shard loss degrades recall gracefully (no crash),
hedged fetches tame the p99 tail, cache-vs-failure interaction,
smooth degraded recall, elastic router behavior."""
import numpy as np
import pytest

from repro.core.distributed import ShardedServing
from repro.core.search import SearchConfig, search_pag, write_partitions
from repro.data.vectors import recall_at_k
from repro.storage.cache import PartitionCache
from repro.storage.resilience import ResiliencePolicy
from repro.storage.simulator import FaultPlan, ObjectStore, StorageConfig


def _serving(built_pag, ds, kind="mem", n_shards=4, seed=0):
    store = ObjectStore(StorageConfig.preset(kind, seed=seed))
    write_partitions(built_pag, ds.base, store, n_shards=n_shards)
    return ShardedServing(pag=built_pag, store=store, n_shards=n_shards,
                          dim=ds.d)


def test_shard_failure_graceful(built_pag, small_ds):
    srv = _serving(built_pag, small_ds)
    cfg = SearchConfig(L=64, k=10, n_probe_max=64)
    ids, _, _ = srv.search(small_ds.queries, cfg)
    base = recall_at_k(ids, small_ds.gt_ids, 10)

    srv.kill_shard(0)   # 1/4 of partitions gone
    ids, _, st = srv.search(small_ds.queries, cfg)
    degraded = recall_at_k(ids, small_ds.gt_ids, 10)
    # no exception; recall drops at most ~ the lost partition fraction
    # (redundant copies on other shards absorb part of the loss)
    assert degraded >= base - 0.30, (base, degraded)
    assert degraded >= 0.5

    srv.revive()
    ids, _, _ = srv.search(small_ds.queries, cfg)
    assert recall_at_k(ids, small_ds.gt_ids, 10) >= base - 1e-9


def test_redundancy_absorbs_failures(built_pag, small_ds):
    """GR redundancy: recall after 1-shard loss stays above the naive
    expectation of losing 1/n_shards of all residuals."""
    srv = _serving(built_pag, small_ds)
    cfg = SearchConfig(L=64, k=10, n_probe_max=64)
    srv.kill_shard(1)
    ids, _, _ = srv.search(small_ds.queries, cfg)
    degraded = recall_at_k(ids, small_ds.gt_ids, 10)
    assert degraded > 0.75 * 0.9  # redundant copies land on other shards


def test_cache_hit_masks_dead_shard(built_pag, small_ds):
    """A PartitionCache hit can serve a partition whose shard has since
    died — that's a feature: warm caches carry recall through an
    outage."""
    srv = _serving(built_pag, small_ds)
    cache = PartitionCache(10 ** 9)
    cfg = SearchConfig(L=64, k=10, n_probe_max=64, cache=cache)
    cfg_nocache = SearchConfig(L=64, k=10, n_probe_max=64)
    ids_base, _, _ = srv.search(small_ds.queries, cfg)  # warms the cache
    base = recall_at_k(ids_base, small_ds.gt_ids, 10)

    srv.kill_shard(0)
    ids_cold, _, st_cold = srv.search(small_ds.queries, cfg_nocache)
    rec_cold = recall_at_k(ids_cold, small_ds.gt_ids, 10)
    ids_warm, _, st_warm = srv.search(small_ds.queries, cfg)
    rec_warm = recall_at_k(ids_warm, small_ds.gt_ids, 10)

    assert np.array_equal(ids_warm, ids_base)   # outage fully masked
    assert rec_warm >= base - 1e-9
    assert rec_warm >= rec_cold                 # and beats the cold path
    assert sum(d.n_probes_lost for d in st_warm.degraded) \
        < sum(d.n_probes_lost for d in st_cold.degraded)


@pytest.mark.parametrize("resilience", [None, ResiliencePolicy()],
                         ids=["bare", "resilient"])
def test_corrupted_objects_never_cached(built_pag, small_ds, resilience):
    """Payload corruption detected via the put-time checksum must not be
    admitted to the cache (a cached corrupt object would poison every
    later hit), on both admission paths of the coalesced wave: the bare
    plane checks the checksum at admission, the resilient chain before
    it hands a payload back."""
    plan = FaultPlan(corrupt_p=0.35, sticky=True, seed=2)
    store = ObjectStore(StorageConfig.preset("mem"), fault_plan=plan)
    write_partitions(built_pag, small_ds.base, store, n_shards=4)
    cache = PartitionCache(10 ** 9)
    cfg = SearchConfig(L=64, k=10, n_probe_max=64, cache=cache,
                       resilience=resilience)
    search_pag(built_pag, small_ds.d, small_ds.queries, store, cfg,
               n_shards=4)
    assert cache._data                        # clean objects were cached
    assert all(store.verify(key, val) for key, val in cache._data.items())
    # and with sticky corruption some fetches were corrupt for sure
    n_parts = built_pag.n_parts
    assert any(not store.verify(f"part/{pid % 4}/{pid}",
                                store.get(f"part/{pid % 4}/{pid}")[0])
               for pid in range(n_parts))


def test_recall_degrades_smoothly_with_dead_shards(built_pag, small_ds):
    """on_missing="skip" with F dead shards out of S: recall stays >=
    (1 - F/S) * baseline (redundant copies usually do much better), and
    dead_shard_fallback=False raises instead of silently degrading."""
    S = 4
    srv = _serving(built_pag, small_ds, n_shards=S)
    cfg = SearchConfig(L=64, k=10, n_probe_max=64)
    ids, _, _ = srv.search(small_ds.queries, cfg)
    base = recall_at_k(ids, small_ds.gt_ids, 10)
    prev = base
    for F in (1, 2, 3):
        srv.kill_shard(F - 1)
        ids_f, _, st = srv.search(small_ds.queries, cfg)
        rec = recall_at_k(ids_f, small_ds.gt_ids, 10)
        assert rec >= (1 - F / S) * base - 1e-9, (F, base, rec)
        assert rec <= prev + 1e-9   # monotone in the damage
        assert st.n_degraded_queries() > 0
        prev = rec
    srv.revive()

    store = ObjectStore(StorageConfig.preset("mem"))
    write_partitions(built_pag, small_ds.base, store, n_shards=S)
    store.kill_prefix("part/0/")
    with pytest.raises(KeyError):
        search_pag(built_pag, small_ds.d, small_ds.queries, store,
                   SearchConfig(L=64, k=10, n_probe_max=64),
                   n_shards=S, dead_shard_fallback=False)


def test_hedging_improves_tail(built_pag, small_ds):
    cfg_plain = SearchConfig(L=32, k=10, n_probe_max=16, mode="sync")
    cfg_hedge = SearchConfig(L=32, k=10, n_probe_max=16, mode="sync",
                             hedge_after_s=3e-3)
    srv1 = _serving(built_pag, small_ds, kind="dfs", seed=5)
    _, _, st_plain = srv1.search(small_ds.queries, cfg_plain)
    srv2 = _serving(built_pag, small_ds, kind="dfs", seed=5)
    _, _, st_hedge = srv2.search(small_ds.queries, cfg_hedge)
    assert st_hedge.p99() <= st_plain.p99() * 1.05
    assert max(st_hedge.latencies_s) <= max(st_plain.latencies_s)
