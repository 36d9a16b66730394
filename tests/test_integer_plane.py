"""Integer-vector deployments (uint8 / int8, BIGANN-style) served in their
own type: residual objects, graph, pools, pads and the masked scan kernel,
checked against numpy int64 brute force; float32 objects stay byte for
byte as they were."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import load_index, save_index
from repro.core.pag import build_pag
from repro.core.search import SearchConfig, search_pag, write_partitions
from repro.core.distributed import ShardedServing
from repro.data.vectors import make_dataset
from repro.dataplane.plan import KeySpace, pack_ids, unpack_ids
from repro.kernels import ops, ref
from repro.serving.engine import AnnsFrontend
from repro.storage.resilience import ResiliencePolicy
from repro.storage.simulator import FaultPlan, ObjectStore, StorageConfig

N, D, NQ, S = 1500, 32, 40, 4
RANGES = {"uint8": (0, 255), "int8": (-128, 127)}
BIG_IDS = np.array([0, 1, 255, 256, 2 ** 24 + 1, 2 ** 31 - 2, 2 ** 31 - 1])
CFG = SearchConfig(L=64, k=10, n_probe_max=32)


def _to_int(dtype, base, queries):
    """The float mixture mapped onto the type's range by one affine map."""
    lo, hi = RANGES[dtype]
    b0, b1 = float(base.min()), float(base.max())

    def f(v):
        return np.clip(np.rint(lo + (v - b0) * (hi - lo) / (b1 - b0)),
                       lo, hi).astype(dtype)
    return f(base), f(queries)


def _deployment(dtype):
    """A tiny integer base with all-lo, all-hi and tied rows, and queries
    that include an all-lo, an all-hi and a copy of a base row."""
    ds = make_dataset("clustered", n=N, d=D, n_queries=NQ, seed=3)
    base, queries = _to_int(dtype, ds.base, ds.queries)
    lo, hi = RANGES[dtype]
    base[0], base[1] = lo, hi
    base[2:6] = base[100]                        # ties at distance 0
    queries[0], queries[1], queries[2] = lo, hi, base[100]
    queries[3] = base[777]                       # d2 exactly 0
    return base, queries


def _exact_d2(base, queries, ids):
    diff = base[ids].astype(np.int64) - queries[:, None, :].astype(np.int64)
    return (diff * diff).sum(-1)


@pytest.fixture(scope="module", params=sorted(RANGES))
def deployed(request):
    dtype = request.param
    base, queries = _deployment(dtype)
    pag = build_pag(base, p=0.2, lam=3.0, redundancy=4)
    store = ObjectStore(StorageConfig.preset("dfs", seed=1))
    write_partitions(pag, base, store, n_shards=S)
    return dtype, base, queries, pag, store


def _serve(pag, store, queries, cfg=CFG):
    """(ids, d2) of ``queries`` submitted one by one to ``AnnsFrontend``."""
    srv = ShardedServing(pag=pag, store=store, n_shards=S, dim=D)
    fe = AnnsFrontend(srv, cfg, max_batch=16)
    tickets = [fe.submit(q) for q in queries]
    fe.flush()
    return (np.stack([fe.results[t][0] for t in tickets]),
            np.stack([fe.results[t][1] for t in tickets]))


def _assert_exact(base, queries, ids, d2, k=10):
    assert ((ids >= 0) & (ids < len(base))).all()
    assert all(len(set(r.tolist())) == k for r in ids)
    np.testing.assert_array_equal(d2.astype(np.float64),
                                  _exact_d2(base, queries, ids))


# ------------------------------------------------------------ served path
def test_served_distances_are_exact_integers(deployed):
    dtype, base, queries, pag, store = deployed
    ids, d2 = _serve(pag, store, queries)
    _assert_exact(base, queries, ids, d2)
    assert d2[2, 0] == 0 and d2[3, 0] == 0       # a query on a base row
    tied = {100, 2, 3, 4, 5}
    assert set(ids[2, :5].tolist()) == tied and (d2[2, :5] == 0).all()
    assert ids[3, 0] == 777
    # against the exact k nearest: the served 10th is never nearer
    want = np.sort(_exact_d2(base, queries, np.tile(np.arange(N), (NQ, 1))),
                   axis=1)[:, :10]
    assert (d2.astype(np.int64) >= want).all()
    assert np.mean(d2.astype(np.int64) == want) > 0.9


def test_objects_graph_and_launches_stay_in_the_base_type(deployed,
                                                          monkeypatch):
    dtype, base, queries, pag, store = deployed
    obj = store._data["part/0/0"]
    assert obj.dtype == dtype and obj.shape[1] == 4 + D
    assert obj.nbytes == int(pag.pcount[0]) * (4 + D)
    seen = []
    orig = ops.l2_topk_masked

    def launch(q, pools, ids, **kw):
        seen.append((q.dtype, pools.dtype))
        return orig(q, pools, ids, **kw)
    monkeypatch.setattr(ops, "l2_topk_masked", launch)
    _serve(pag, store, queries[:8])
    assert seen == [(np.dtype(dtype), np.dtype(dtype))]


def test_device_arrays_upload_the_base_type(deployed):
    dtype, _, _, pag, _ = deployed
    A_dev, nbrs_dev, _, _ = pag.pg.device_arrays()
    assert A_dev.dtype == dtype and pag.pg.A.dtype == np.float32
    assert A_dev.nbytes == pag.pg.m_cap * D
    np.testing.assert_array_equal(np.asarray(A_dev).astype(np.float32),
                                  pag.pg.A)
    fpag = build_pag(make_dataset("clustered", n=300, d=8, n_queries=2,
                                  seed=0).base, p=0.2, lam=3.0)
    assert fpag.pg.dtype == "float32"
    assert fpag.pg.device_arrays()[0].dtype == jnp.float32


def test_other_query_types_and_bases_are_refused(deployed):
    dtype, base, queries, pag, store = deployed
    with pytest.raises(ValueError, match="float32 queries"):
        search_pag(pag, D, queries.astype(np.float32), store, CFG,
                   n_shards=S)
    with pytest.raises(ValueError, match="float32 rows"):
        write_partitions(pag, base.astype(np.float32),
                         ObjectStore(StorageConfig.preset("mem")))


def test_index_save_and_load_keep_the_type(deployed, tmp_path):
    dtype, base, queries, pag, store = deployed
    save_index(str(tmp_path), pag)
    back = load_index(str(tmp_path))
    assert back.pg.dtype == dtype and back.arrays()["A"].dtype == dtype
    assert back.pg.A.dtype == np.float32
    np.testing.assert_array_equal(back.pg.A, pag.pg.A)
    ids, d2 = _serve(pag, store, queries[:16])
    ids2, d22 = _serve(back, store, queries[:16])
    np.testing.assert_array_equal(ids, ids2)
    np.testing.assert_array_equal(d2, d22)


# ------------------------------------------------------------ the payload
@pytest.mark.parametrize("dtype", ["float32", "uint8", "int8"])
def test_payload_round_trip_keeps_ids_to_2_31(dtype):
    rng = np.random.default_rng(0)
    vecs = (rng.standard_normal((len(BIG_IDS), 16)).astype(np.float32)
            if dtype == "float32" else
            rng.integers(*RANGES[dtype], size=(len(BIG_IDS), 16),
                         endpoint=True).astype(dtype))
    ks = KeySpace(dtype=dtype)
    obj = ks.pack(BIG_IDS, vecs)
    assert obj.dtype == dtype and obj.nbytes == len(BIG_IDS) * (4 + 16 * (
        4 if dtype == "float32" else 1))
    ids, back = ks.unpack(obj)
    np.testing.assert_array_equal(ids, BIG_IDS)
    np.testing.assert_array_equal(back, vecs)
    np.testing.assert_array_equal(unpack_ids(pack_ids(BIG_IDS, dtype)),
                                  BIG_IDS)
    empty_ids, empty = ks.unpack(ks.pack(BIG_IDS[:0], vecs[:0]))
    assert empty_ids.shape == (0,) and empty.shape == (0, 16)


def _parent_float_object(ids, x):
    """The float32 residual object as it was written before objects took
    the base's type: [cnt, 1 + d], the id bit-cast into column 0."""
    obj = np.zeros((len(ids), x.shape[1] + 1), np.float32)
    obj[:, 0] = np.ascontiguousarray(ids, np.int32).view(np.float32)
    obj[:, 1:] = x[ids]
    return obj


def test_float32_objects_are_byte_identical_to_the_old_layout():
    ds = make_dataset("clustered", n=900, d=D, n_queries=2, seed=1)
    pag = build_pag(ds.base, p=0.2, lam=3.0, redundancy=4)
    store = ObjectStore(StorageConfig.preset("mem"))
    write_partitions(pag, ds.base, store, n_shards=S, replicas=2)
    frozen = ObjectStore(StorageConfig.preset("mem"))
    for key in store._data:
        pid = int(key.split("/")[2])
        obj = _parent_float_object(pag.plist[pid, :pag.pcount[pid]],
                                   ds.base)
        frozen.put(key, obj)
        got = store._data[key]
        assert got.dtype == np.float32 and got.shape == obj.shape
        assert got.tobytes() == obj.tobytes()
    assert store._crc == frozen._crc
    assert store.total_bytes() == frozen.total_bytes()
    ids = BIG_IDS.astype(np.int32)
    assert pack_ids(ids).tobytes() == ids.view(np.float32).tobytes()


# ------------------------------------------------- faults, replicas, PQ
def test_a_corrupted_integer_object_is_caught_by_its_checksum(deployed):
    dtype, base, queries, pag, store = deployed
    ids_clean, d2_clean = _serve(pag, store, queries)
    faulty = ObjectStore(StorageConfig.preset("dfs", seed=1),
                         FaultPlan(corrupt_p=1.0, sticky=True, seed=2))
    write_partitions(pag, base, faulty, n_shards=S)
    v, _ = faulty.get("part/0/0")
    assert v.dtype == dtype and not faulty.verify("part/0/0", v)

    store2 = ObjectStore(StorageConfig.preset("dfs", seed=1),
                         FaultPlan(corrupt_p=0.3, seed=5))
    write_partitions(pag, base, store2, n_shards=S, replicas=2)
    cfg = dataclasses.replace(CFG, replicas=2, resilience=ResiliencePolicy(
        max_attempts_per_replica=4, max_total_attempts=16, deadline_s=5.0,
        breaker_fail_threshold=10 ** 6))
    ids, d2, stats = search_pag(pag, D, queries, store2, cfg, n_shards=S)
    assert sum(d.corruptions for d in stats.degraded) > 0
    assert sum(d.n_probes_lost for d in stats.degraded) == 0
    np.testing.assert_array_equal(ids, ids_clean)
    np.testing.assert_array_equal(d2, d2_clean)


def test_two_replicas_serve_through_a_lost_shard(deployed):
    dtype, base, queries, pag, store = deployed
    ids_clean, d2_clean = _serve(pag, store, queries)
    store2 = ObjectStore(StorageConfig.preset("dfs", seed=1))
    write_partitions(pag, base, store2, n_shards=S, replicas=2)
    assert store2._data["part/1/0/r1"].dtype == dtype
    srv = ShardedServing(pag=pag, store=store2, n_shards=S, dim=D,
                         replicas=2).enable_resilience(ResiliencePolicy())
    srv.kill_shard(0)
    ids, d2, stats = srv.search(queries, CFG)
    assert sum(d.n_probes_lost for d in stats.degraded) == 0
    np.testing.assert_array_equal(ids, ids_clean)
    np.testing.assert_array_equal(d2, d2_clean)


def test_pq_plane_on_an_integer_base(deployed):
    dtype, base, queries, pag, _ = deployed
    store = ObjectStore(StorageConfig.preset("dfs", seed=1))
    write_partitions(pag, base, store, n_shards=S, compression="pq", pq_m=8)
    assert store._data["part/0/0"].dtype == dtype
    assert store._data["part/0/0/pq"].dtype == np.uint8
    cfg = dataclasses.replace(CFG, compression="pq", rerank_k=32)
    ids, d2 = _serve(pag, store, queries, cfg=cfg)
    _assert_exact(base, queries, ids, d2)
    assert d2[3, 0] == 0


# ----------------------------------------------------------- the kernel
@pytest.mark.parametrize("dtype", ["uint8", "int8"])
@pytest.mark.parametrize("qn,c,d,k,block", [
    (8, 300, 128, 10, 128),     # BIGANN's width, ragged blocks
    (5, 40, 32, 16, 32),        # k > some rows' pools
])
def test_masked_kernel_is_exact_on_integer_pools(dtype, qn, c, d, k, block):
    lo, hi = RANGES[dtype]
    rng = np.random.default_rng(qn * c)
    q = rng.integers(lo, hi, size=(qn, d), endpoint=True).astype(dtype)
    pools = rng.integers(lo, hi, size=(qn, c, d), endpoint=True).astype(
        dtype)
    q[0], pools[0, 0], pools[0, 1] = lo, hi, lo  # extremes: d2 0 and max
    pools[1, 5] = pools[1, 9] = q[1]             # a tie at distance 0
    ids = rng.permutation(qn * c).reshape(qn, c).astype(np.int32)
    lens = np.linspace(c // 3, c, qn).astype(int)
    ids = np.where(np.arange(c)[None, :] < lens[:, None], ids, -1)
    d2, oi = ops.l2_topk_masked(jnp.asarray(q), jnp.asarray(pools),
                                jnp.asarray(ids), k=k, block_c=block,
                                interpret=True)
    d2r, oir = ref.l2_topk_masked_ref(jnp.asarray(q), jnp.asarray(pools),
                                      jnp.asarray(ids), k)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2r))
    exact = ((pools.astype(np.int64) - q[:, None].astype(np.int64)) ** 2
             ).sum(-1)
    want = np.sort(np.where(ids >= 0, exact, np.iinfo(np.int64).max),
                   axis=1)[:, :k]
    got_d2, got_i = np.asarray(d2), np.asarray(oi)
    for r in range(qn):
        real = got_d2[r] < 3.4e38
        assert real.sum() == min(k, lens[r])
        np.testing.assert_array_equal(got_d2[r][real], want[r][:real.sum()])
        pos = {int(i): j for j, i in enumerate(ids[r]) if i >= 0}
        np.testing.assert_array_equal(
            got_d2[r][real], exact[r][[pos[int(i)] for i in got_i[r][real]]])
    assert got_d2[0, 0] == 0
    assert set(got_i[1, :2].tolist()) == {ids[1, 5], ids[1, 9]}
    assert set(np.asarray(oir)[1, :2].tolist()) == {ids[1, 5], ids[1, 9]}


def test_masked_kernel_refuses_mixed_types():
    q = jnp.zeros((8, 16), jnp.uint8)
    with pytest.raises(TypeError, match="uint8 queries"):
        ops.l2_topk_masked(q, jnp.zeros((8, 32, 16), jnp.float32),
                           jnp.zeros((8, 32), jnp.int32), k=4,
                           interpret=True)
