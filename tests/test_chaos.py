"""Chaos harness (seeded, deterministic, smoke-sized): the availability
claim under fault injection — replication + retry/failover keeps recall
and tail latency up where the bare skip-path loses partitions. Runs in
the fast tier by default (marker: chaos, not slow)."""
import dataclasses

import numpy as np
import pytest
from reference_search import assert_matches_reference, reference_search

from repro.core.search import SearchConfig, search_pag, write_partitions
from repro.data.vectors import recall_at_k
from repro.storage.resilience import ResiliencePolicy
from repro.storage.simulator import FaultPlan, ObjectStore, StorageConfig

pytestmark = pytest.mark.chaos

POLICY = ResiliencePolicy(max_attempts_per_replica=2,
                          request_timeout_s=0.05, deadline_s=0.5)


def _store(built_pag, ds, kind="dfs", seed=1, plan=None, replicas=1,
           n_shards=4):
    store = ObjectStore(StorageConfig.preset(kind, seed=seed),
                        fault_plan=plan)
    write_partitions(built_pag, ds.base, store, n_shards=n_shards,
                     replicas=replicas)
    return store


def _search(built_pag, ds, store, **cfg_kw):
    cfg = SearchConfig(L=64, k=10, n_probe_max=32, **cfg_kw)
    return search_pag(built_pag, ds.d, ds.queries, store, cfg, n_shards=4)


def test_availability_claim_r2_vs_r1(built_pag, small_ds):
    """Acceptance criterion: R=2 + resilience at 10% sticky faults on the
    DFS profile holds recall within 1% of fault-free and p99 within 3x;
    R=1 under the same faults shows measurable recall loss."""
    ids_ff, _, st_ff = _search(built_pag, small_ds,
                               _store(built_pag, small_ds, replicas=2))
    rec_ff = recall_at_k(ids_ff, small_ds.gt_ids, 10)
    p99_ff = st_ff.p99()

    plan = FaultPlan(transient_p=0.10, sticky=True, seed=17)
    ids_r2, _, st_r2 = _search(
        built_pag, small_ds,
        _store(built_pag, small_ds, plan=plan, replicas=2),
        replicas=2, resilience=POLICY)
    rec_r2 = recall_at_k(ids_r2, small_ds.gt_ids, 10)
    assert rec_r2 >= rec_ff - 0.01, (rec_ff, rec_r2)
    assert st_r2.p99() <= 3 * p99_ff, (p99_ff, st_r2.p99())
    # failovers did the work and are visible in the stats
    assert st_r2.total_failovers() > 0

    ids_r1, _, st_r1 = _search(
        built_pag, small_ds,
        _store(built_pag, small_ds, plan=plan, replicas=1),
        replicas=1, resilience=POLICY)
    rec_r1 = recall_at_k(ids_r1, small_ds.gt_ids, 10)
    assert rec_r1 < rec_r2 - 0.002, (rec_r1, rec_r2)   # measurable loss
    assert st_r1.n_degraded_queries() > 0
    assert any(d.n_probes_lost > 0 for d in st_r1.degraded)


def test_fault_plan_resolves_to_reference(built_pag, small_ds):
    """Under a seeded fault plan (sticky transients + corruption, R=2),
    every query whose probes all resolved gets the fault-free answer of
    the plain reference, which reads the base array and no store:
    retries and failovers land on clean copies of the same payloads.
    Circuit breakers are taken out of the loop (huge threshold): their
    state depends on the request history, not on fault resolution."""
    plan = FaultPlan(transient_p=0.15, corrupt_p=0.1, sticky=True, seed=5)
    pol = dataclasses.replace(POLICY, breaker_fail_threshold=10 ** 9)
    store = _store(built_pag, small_ds, kind="mem", plan=plan, replicas=2)
    ids, d2, st = _search(built_pag, small_ds, store, replicas=2,
                          resilience=pol)
    cfg = SearchConfig(L=64, k=10, n_probe_max=32)
    ref = reference_search(built_pag, small_ds.base, small_ds.queries, cfg)
    whole = [qi for qi, d in enumerate(st.degraded) if d.n_probes_lost == 0]
    assert whole    # 12 of the 100 queries at this seed
    assert_matches_reference(ids, d2, ref, small_ds.base, small_ds.queries,
                             rows=whole)
    for qi in whole:
        assert st.n_probes[qi] == st.degraded[qi].n_probes_wanted
    # the recovery plane actually fired somewhere in the batch
    assert st.total_failovers() + st.total_retries() > 0
    assert sum(d.corruptions for d in st.degraded) > 0


def test_blip_faults_recovered_by_retry_alone(built_pag, small_ds):
    """Non-sticky transients at R=1: retry-with-backoff recovers them
    with zero recall loss vs fault-free, and the retries are charged
    (latency accounting) and reported (DegradedInfo)."""
    ids_ff, _, _ = _search(built_pag, small_ds,
                           _store(built_pag, small_ds, kind="mem"))
    plan = FaultPlan(transient_p=0.15, sticky=False, seed=11)
    store = _store(built_pag, small_ds, kind="mem", plan=plan)
    pol = dataclasses.replace(POLICY, max_attempts_per_replica=5)
    ids, _, st = _search(built_pag, small_ds, store, resilience=pol)
    assert np.array_equal(ids, ids_ff)
    assert st.total_retries() > 0
    retried = [qi for qi, d in enumerate(st.degraded) if d.retries]
    assert retried
    # backoff waits show up on the event clock of retried queries
    # (>= one backoff, modulo the +-jitter_frac deterministic jitter)
    assert all(st.latencies_s[qi] >=
               (1 - POLICY.jitter_frac) * POLICY.base_backoff_s
               for qi in retried)


def test_degraded_info_plumbed_through_frontend(built_pag, small_ds):
    """AnnsFrontend exposes per-ticket DegradedInfo."""
    from repro.core.distributed import ShardedServing
    from repro.serving.engine import AnnsFrontend

    plan = FaultPlan(transient_p=0.2, sticky=True, seed=3)
    store = _store(built_pag, small_ds, kind="mem", plan=plan, replicas=2)
    srv = ShardedServing(pag=built_pag, store=store, n_shards=4,
                         dim=small_ds.d, replicas=2)
    srv.enable_resilience(POLICY)
    fe = AnnsFrontend(srv, SearchConfig(L=64, k=10, n_probe_max=32),
                      max_batch=64)
    tickets = [fe.submit(small_ds.queries[i]) for i in range(16)]
    fe.flush()
    assert set(tickets) <= set(fe.degraded)
    total = sum(fe.degraded[t].failovers + fe.degraded[t].retries
                for t in tickets)
    assert total > 0
    assert all(fe.degraded[t].n_probes_wanted > 0 for t in tickets)


def test_breaker_persists_across_batches(built_pag, small_ds):
    """A long-lived ResilientStore on the serving tier: a dead shard
    trips its breaker in batch 1; batch 2 routes past it via breaker
    skips instead of burning error-retry budget."""
    from repro.core.distributed import ShardedServing

    store = _store(built_pag, small_ds, kind="mem", replicas=2)
    srv = ShardedServing(pag=built_pag, store=store, n_shards=4,
                         dim=small_ds.d, replicas=2)
    pol = dataclasses.replace(POLICY, max_attempts_per_replica=1,
                              breaker_fail_threshold=2,
                              breaker_cooldown_requests=1000)
    srv.enable_resilience(pol)
    srv.kill_shard(0)
    cfg = SearchConfig(L=64, k=10, n_probe_max=32)
    _, _, st1 = srv.search(small_ds.queries[:50], cfg)
    assert srv.resilient.n_open_breakers() == 1
    assert st1.degraded[0].breakers_open in (0, 1)
    _, _, st2 = srv.search(small_ds.queries[50:], cfg)
    assert sum(d.breaker_skips for d in st2.degraded) > 0
    assert all(d.breakers_open == 1 for d in st2.degraded)


@pytest.mark.slow
def test_chaos_sweep_full(built_pag, small_ds):
    """Full sweep (slow tier): recall monotonically protected as R grows
    at a fixed 20% sticky fault rate."""
    plan = FaultPlan(transient_p=0.2, sticky=True, seed=23)
    recalls = {}
    for R in (1, 2, 3):
        store = _store(built_pag, small_ds, plan=plan, replicas=R)
        ids, _, _ = _search(built_pag, small_ds, store, replicas=R,
                            resilience=POLICY)
        recalls[R] = recall_at_k(ids, small_ds.gt_ids, 10)
    assert recalls[2] >= recalls[1]
    assert recalls[3] >= recalls[2] - 1e-9
    ids_ff, _, _ = _search(built_pag, small_ds,
                           _store(built_pag, small_ds, replicas=3))
    assert recalls[3] >= recall_at_k(ids_ff, small_ds.gt_ids, 10) - 0.01
