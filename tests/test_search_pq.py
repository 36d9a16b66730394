"""Compressed data plane (v2 PQ payloads): two-stage batched scan
against the plain reference, byte savings, id bit-cast, and fault
semantics."""
import dataclasses

import numpy as np
import pytest
from reference_search import assert_matches_reference, reference_search

from repro.core.pag import build_pag
from repro.core.search import SearchConfig, search_pag, write_partitions
from repro.dataplane.plan import pack_ids, unpack_ids
from repro.storage.cache import PartitionCache
from repro.storage.resilience import ResiliencePolicy, replica_keys
from repro.storage.simulator import FaultPlan, ObjectStore, StorageConfig

S = 4          # shards
D = 64
PQ_M = 8


@pytest.fixture(scope="module")
def pq_env():
    """Clustered dataset with LARGE partitions (cap = lam/p = 800): the
    geometry where the compressed plane pays off — the probe wave covers
    many partitions, the ADC top concentrates in few."""
    rng = np.random.default_rng(0)
    n, nq = 8000, 40
    cents = rng.standard_normal((40, D)).astype(np.float32) * 4
    x = (cents[rng.integers(0, 40, n)]
         + rng.standard_normal((n, D))).astype(np.float32)
    q = (cents[rng.integers(0, 40, nq)]
         + rng.standard_normal((nq, D))).astype(np.float32)
    pag = build_pag(x, p=0.01, k=8, lam=8.0, redundancy=2, seed=0)
    d2 = ((x[None] - q[:, None]) ** 2).sum(-1)
    gt = np.argsort(d2, axis=1)[:, :10]
    store = ObjectStore(StorageConfig.preset("dfs"))
    cb = write_partitions(pag, x, store, n_shards=S, compression="pq",
                          pq_m=PQ_M)
    return pag, x, q, gt, store, cb


def _recall10(ids, gt):
    return float(np.mean([len(set(ids[i, :10]) & set(gt[i])) / 10
                          for i in range(len(gt))]))


def test_pq_plane_matches_reference(pq_env):
    """Acceptance: under compression="pq" the plane returns the exact
    top-k of the beam plus the partitions covering each query's ADC-top
    ``rerank_k``, as the plain reference ranks and reads them."""
    pag, x, q, gt, store, cb = pq_env
    cfg = SearchConfig(compression="pq", rerank_k=16, n_probe_max=32)
    ids, d2, st = search_pag(pag, D, q, store, cfg, n_shards=S)
    ref = reference_search(pag, x, q, cfg, codebook=cb)
    assert_matches_reference(ids, d2, ref, x, q)
    assert all(d.n_probes_lost == 0 for d in st.degraded)


def test_pq_cuts_bytes_8x_with_recall_within_1pct(pq_env):
    """Acceptance: on the DFS profile the compressed plane fetches >= 8x
    fewer bytes per query than the float plane, with recall@10 within 1%
    (exact rerank). Queries go one at a time, so no cross-query
    coalescing amortizes either bill."""
    pag, x, q, gt, store, cb = pq_env
    q, gt = q[:32], gt[:32]

    def one_at_a_time(cfg):
        """(ids, bytes fetched per query) of ``q`` sent one by one. The
        PQ codebook is index metadata that every call fetches again; the
        stream counts it once, as one batch of the same queries does."""
        rows, nbytes = [], 0
        for i in range(len(q)):
            b0 = store.bytes_fetched
            rows.append(search_pag(pag, D, q[i:i + 1], store, cfg,
                                   n_shards=S)[0])
            nbytes += store.bytes_fetched - b0
        if cfg.compression == "pq":
            nbytes -= (len(q) - 1) * cb.centroids.nbytes
        return np.concatenate(rows), nbytes / len(q)

    # a wide scan block only rounds launch widths coarser (fewer shapes
    # to compile one query at a time); the fetched bytes do not see it
    ids_f, bytes_float = one_at_a_time(
        SearchConfig(n_probe_max=32, scan_block=2048))
    ids_c, bytes_pq = one_at_a_time(
        SearchConfig(compression="pq", rerank_k=64, n_probe_max=32,
                     scan_block=2048))

    ratio = bytes_float / bytes_pq
    r_f, r_c = _recall10(ids_f, gt), _recall10(ids_c, gt)
    assert ratio >= 8.0, f"bytes ratio {ratio:.2f}x < 8x"
    assert r_c >= r_f - 0.01, f"recall {r_c:.3f} vs float {r_f:.3f}"


def test_pack_unpack_ids_exact_beyond_2pow24():
    ids = np.array([0, 1, 2 ** 24 + 1, 2 ** 24 + 12345, 2 ** 31 - 1],
                   np.int64)
    assert (unpack_ids(pack_ids(ids)) == ids).all()
    # the old float VALUE cast loses exactly these ids
    assert (ids.astype(np.float32).astype(np.int64) != ids).any()


class _OffsetRows:
    """x wrapper addressed by offset ids (billion-scale id simulation:
    the dataset slice of a distributed build whose global ids start at
    ``off``)."""

    def __init__(self, x, off):
        self.x, self.off = x, off

    @property
    def shape(self):
        return self.x.shape

    def __getitem__(self, ids):
        return self.x[np.asarray(ids) - self.off]

    def __array__(self, dtype=None):  # PQ training sees plain vectors
        return self.x if dtype is None else self.x.astype(dtype)


@pytest.mark.parametrize("compression", ["none", "pq"])
def test_billion_scale_ids_survive_storage(built_pag, small_ds,
                                           compression):
    """Regression: the id column bit-casts int32 (exact) instead of a
    float value cast (exact only below 2^24). Shift every id by
    2^24 + 12345 and require results == baseline + shift."""
    off = 2 ** 24 + 12345
    pag, x, q = built_pag, small_ds.base, small_ds.queries[:20]
    store = ObjectStore(StorageConfig.preset("mem"))
    write_partitions(pag, x, store, n_shards=S,
                     compression=compression, pq_m=8)
    cfg = SearchConfig(compression=compression, rerank_k=32)
    base_ids, base_d2, _ = search_pag(pag, x.shape[1], q, store, cfg,
                                      n_shards=S)

    big = dataclasses.replace(
        pag,
        node_src=np.where(pag.node_src >= 0, pag.node_src + off, -1)
        .astype(np.int64),
        plist=np.where(pag.plist >= 0, pag.plist + off, -1)
        .astype(np.int64))
    store2 = ObjectStore(StorageConfig.preset("mem"))
    write_partitions(big, _OffsetRows(x, off), store2, n_shards=S,
                     compression=compression, pq_m=8)
    big_ids, big_d2, _ = search_pag(big, x.shape[1], q, store2, cfg,
                                    n_shards=S)
    valid = base_ids >= 0
    np.testing.assert_array_equal(big_ids[valid], base_ids[valid] + off)
    np.testing.assert_allclose(big_d2, base_d2, rtol=1e-5)


def test_lost_code_object_degrades_like_lost_partition(pq_env):
    pag, x, q, gt, store, _ = pq_env
    # kill the PRIMARY code object of every partition on shard 0 (their
    # float siblings survive: the probe wave still can't use them)
    for pid in range(pag.n_parts):
        store.kill_prefix(f"part/{pid % S}/{pid}/pq")
    try:
        cfg = SearchConfig(compression="pq", rerank_k=16, n_probe_max=32)
        ids, _, stats = search_pag(pag, D, q, store, cfg, n_shards=S)
        lost = sum(d.n_probes_lost for d in stats.degraded)
        assert lost > 0          # code objects gone => probes degraded
        assert ids.shape == (len(q), 10)
        with pytest.raises(KeyError):
            search_pag(pag, D, q, store, cfg, n_shards=S,
                       dead_shard_fallback=False)
    finally:
        store.revive_all()


def test_lost_codebook_degrades_to_beam_only(pq_env):
    pag, x, q, gt, store, _ = pq_env
    store.kill_prefix("part/meta/pq_codebook")
    try:
        cfg = SearchConfig(compression="pq", rerank_k=16, n_probe_max=32)
        ids, _, stats = search_pag(pag, D, q, store, cfg, n_shards=S)
        assert all(d.n_probes_lost == d.n_probes_wanted
                   for d in stats.degraded)     # every probe lost
        assert (np.asarray(stats.n_probes) == 0).all()
        assert ids.shape == (len(q), 10)        # beam-only results
        with pytest.raises(KeyError):
            search_pag(pag, D, q, store, cfg, n_shards=S,
                       dead_shard_fallback=False)
    finally:
        store.revive_all()


def test_corrupt_codes_never_cached(pq_env):
    pag, x, q, gt, store, _ = pq_env
    store.set_fault_plan(FaultPlan(corrupt_p=1.0, sticky=True, seed=3))
    try:
        cache = PartitionCache(64 * 1024 * 1024)
        cfg = SearchConfig(compression="pq", rerank_k=16, n_probe_max=32,
                           cache=cache)
        search_pag(pag, D, q, store, cfg, n_shards=S)
        assert len(cache._data) == 0    # nothing corrupt admitted
    finally:
        store.set_fault_plan(None)


def test_corrupt_codes_recovered_by_replicas(pq_env):
    """Transient corruption: the resilient chain detects it against the
    put-time checksum, retries / fails over to clean replicas, and the
    results match the clean run exactly."""
    pag, x, q, gt, store, _ = pq_env
    clean_cfg = SearchConfig(compression="pq", rerank_k=16,
                             n_probe_max=32)
    ids_clean, _, _ = search_pag(pag, D, q, store, clean_cfg, n_shards=S)

    store2 = ObjectStore(StorageConfig.preset("dfs"))
    write_partitions(pag, x, store2, n_shards=S, replicas=2,
                     compression="pq", pq_m=PQ_M)
    store2.set_fault_plan(FaultPlan(corrupt_p=0.3, seed=5))
    cfg = SearchConfig(compression="pq", rerank_k=16, n_probe_max=32,
                       replicas=2,
                       resilience=ResiliencePolicy(
                           max_attempts_per_replica=3,
                           max_total_attempts=12, deadline_s=5.0))
    ids, _, stats = search_pag(pag, D, q, store2, cfg, n_shards=S)
    assert sum(d.corruptions for d in stats.degraded) > 0  # faults hit
    assert sum(d.n_probes_lost for d in stats.degraded) == 0
    np.testing.assert_array_equal(ids, ids_clean)


def test_v2_payload_layout(pq_env):
    pag, x, q, gt, store, _ = pq_env
    store2 = ObjectStore(StorageConfig.preset("mem"))
    cb = write_partitions(pag, x, store2, n_shards=S, replicas=2,
                          compression="pq", pq_m=PQ_M)
    assert cb.centroids.shape == (PQ_M, 256, D // PQ_M)
    arr, _ = store2.get("part/meta/pq_codebook")
    np.testing.assert_array_equal(arr, cb.centroids)
    store2.get("part/meta/pq_codebook/r1")  # replicated metadata
    pid = int(np.argmax(pag.pcount))
    cnt = int(pag.pcount[pid])
    keys = replica_keys("part", pid, S, 2, obj="pq")
    assert keys[0] == f"part/{pid % S}/{pid}/pq"
    assert keys[1] == f"part/{(pid + 1) % S}/{pid}/pq/r1"
    for key in keys:
        codes, _ = store2.get(key)
        assert codes.dtype == np.uint8 and codes.shape == (cnt, PQ_M)
        assert store2.verify(key, codes)    # put-time checksums
    fl, _ = store2.get(replica_keys("part", pid, S, 2)[0])
    assert fl.dtype == np.float32 and fl.shape == (cnt, D + 1)


def test_cache_stats_surface_in_search_stats(pq_env):
    pag, x, q, gt, store, _ = pq_env
    cache = PartitionCache(64 * 1024 * 1024)
    cfg = SearchConfig(compression="pq", rerank_k=16, n_probe_max=32,
                       cache=cache)
    _, _, st1 = search_pag(pag, D, q, store, cfg, n_shards=S)
    assert st1.cache_hit_rate is not None
    _, _, st2 = search_pag(pag, D, q, store, cfg, n_shards=S)
    assert st2.cache_hit_rate > st1.cache_hit_rate  # warm second pass
    # a tiny budget must evict (codes + codebook exceed it)
    tiny = PartitionCache(8 * 1024)
    cfg2 = SearchConfig(compression="pq", rerank_k=16, n_probe_max=32,
                        cache=tiny)
    _, _, st3 = search_pag(pag, D, q, store, cfg2, n_shards=S)
    assert st3.cache_bytes_evicted > 0
    stats_nocache = search_pag(
        pag, D, q, store,
        SearchConfig(compression="pq", rerank_k=16, n_probe_max=32),
        n_shards=S)[2]
    assert stats_nocache.cache_hit_rate is None
