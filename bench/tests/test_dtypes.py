"""The benchmark serves and checks a deployment in the dtype and metric its
configuration states, on the CPU at small sizes: float32 data, references
and kernel counts are as they were; integer vectors are generated by one
affine map, reach the program uncast and are checked exactly, recall
counting ties at the k-th place; what the harness does not honour is
refused before any work."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import costs  # noqa: E402
import data  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

TINY = {"n": 1200, "n_queries": 128}
INTS = ["uint8", "int8"]
L2_LIMIT = json.loads((BENCH / "limits.json").read_text())["limits"][
    "l2_topk_masked_gap"]


def frozen_clustered(n, d, n_queries, seed, query_noise=0.1):
    """``data.clustered`` and ``data.vectors`` as they were before vectors
    took a dtype: the float32 deployments' data must not move."""
    rng = np.random.default_rng(seed)
    n_clusters = max(n // 400, 8)
    weights = 1.0 / np.arange(1, n_clusters + 1) ** 1.1
    weights /= weights.sum()
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32)
    assign = rng.choice(n_clusters, size=n, p=weights)
    scales = (0.3 + rng.gamma(2.0, 0.3, size=(n_clusters, d))).astype(
        np.float32)
    base = centers[assign] + rng.standard_normal(
        (n, d)).astype(np.float32) * scales[assign]
    q_src = rng.choice(n, size=n_queries, replace=False)
    queries = base[q_src] + query_noise * rng.standard_normal(
        (n_queries, d)).astype(np.float32)
    return base.astype(np.float32), queries.astype(np.float32)


def _config(name, **over):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(TINY, **over)
    return cfg


# ------------------------------------------------------ float32 unchanged
@pytest.mark.parametrize("name", ["deep96-float", "deep96-pq"])
def test_float32_vectors_are_the_frozen_generator(name):
    cfg = _config(name)
    base, queries = data.vectors(cfg)
    want = frozen_clustered(cfg["n"], cfg["d"], cfg["n_queries"],
                            cfg["vectors_seed"], cfg["query_noise"])
    for got, exp in zip((base, queries), want):
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, exp)


class _Launch:
    """An argument of a recorded launch: its shape and dtype."""

    def __init__(self, shape, dtype):
        self.shape, self.dtype = shape, np.dtype(dtype)


@pytest.mark.parametrize("kernel,query,pool,k,today", [
    ("l2_topk_masked", ((64, 96), "float32"), ((64, 3072, 96), "float32"),
     10, (56623104, 76313600)),
    ("pq_adc_masked", ((64, 16, 256), "float32"), ((64, 3072, 16), "uint8"),
     32, (3145728, 4997120))], ids=["l2", "pq"])
def test_costs_of_a_float32_launch_are_unchanged(kernel, query, pool, k,
                                                 today):
    """A launch as the float planes make it is counted as before: by
    ``costs`` called with the shape and k alone, and through the shape
    that ``Capture`` records, which the roofline readers pass on."""
    cap = harness.Capture(0)
    cap._record(kernel, (_Launch(*query), _Launch(*pool), None), {"k": k},
                None)
    (shape,) = cap.shapes[kernel]
    assert costs.KERNELS[kernel](*pool[0], k) == today
    assert costs.KERNELS[kernel](*shape) == today


@pytest.mark.parametrize("pool,query,pool_bytes,query_bytes", [
    ("uint8", "uint8", 1, 1), ("uint8", "float32", 1, 4),
    ("int8", "float32", 1, 4), ("float32", "float32", 4, 4)])
def test_l2_launch_bytes_follow_the_element_width(pool, query, pool_bytes,
                                                  query_bytes):
    q, c, d, k = 64, 3072, 128, 10
    cap = harness.Capture(0)
    cap._record("l2_topk_masked", (_Launch((q, d), query),
                                   _Launch((q, c, d), pool), None), {"k": k},
                None)
    (shape,) = cap.shapes["l2_topk_masked"]
    assert shape == (q, c, d, k, pool_bytes, query_bytes)
    flops, nbytes = costs.l2_topk_masked(*shape)
    assert flops == 3 * q * c * d
    assert nbytes == pool_bytes * q * c * d + 4 * q * c \
        + query_bytes * q * d + 8 * q * k


# ------------------------------------------------------- integer vectors
@pytest.mark.parametrize("dtype", INTS)
def test_integer_vectors_are_the_float_mixture_mapped(dtype):
    cfg = _config("deep96-float", dtype=dtype)
    base, queries = data.vectors(cfg)
    lo, hi = data.INT_RANGES[dtype]
    assert base.dtype == queries.dtype == np.dtype(dtype)
    assert (base.min(), base.max()) == (lo, hi)
    assert queries.min() >= lo and queries.max() <= hi
    again = data.vectors(cfg)
    np.testing.assert_array_equal(base, again[0])
    np.testing.assert_array_equal(queries, again[1])
    other = data.vectors(dict(cfg, vectors_seed=cfg["vectors_seed"] + 1))
    assert not np.array_equal(base, other[0])
    # queries go through the base's map, and are clipped to the range
    fb, fq = frozen_clustered(cfg["n"], cfg["d"], cfg["n_queries"],
                              cfg["vectors_seed"], cfg["query_noise"])
    scale = (hi - lo) / (float(fb.max()) - float(fb.min()))
    for got, f in ((base, fb), (queries, fq)):
        want = np.clip(np.rint(lo + (f.astype(np.float64) - fb.min())
                               * scale), lo, hi)
        np.testing.assert_array_equal(got.astype(np.int64), want)


def test_unknown_dtype_is_not_generated():
    with pytest.raises(ValueError, match="float16"):
        data.vectors(_config("deep96-float", dtype="float16"))


def _extreme_rows(dtype, n=300, q=40, d=128, seed=5):
    """Random rows of ``dtype`` at width d, with all-lo and all-hi rows
    among both base and queries."""
    lo, hi = data.INT_RANGES[dtype]
    rng = np.random.default_rng(seed)
    base = rng.integers(lo, hi + 1, (n, d)).astype(dtype)
    queries = rng.integers(lo, hi + 1, (q, d)).astype(dtype)
    base[0], base[1], queries[0], queries[1] = lo, hi, hi, lo
    return base, queries


def _d2_int64(queries, base):
    diff = queries.astype(np.int64)[:, None, :] - base.astype(np.int64)[None]
    return (diff * diff).sum(-1)


@pytest.mark.parametrize("dtype", INTS)
def test_exact_knn_is_exact_on_integer_vectors(dtype):
    base, queries = _extreme_rows(dtype)
    got = reference.exact_knn(base, queries, 10)
    d2 = _d2_int64(queries, base)
    # ties may come in any order: the distances returned are the nearest
    np.testing.assert_array_equal(
        np.take_along_axis(d2, got, axis=1), np.sort(d2, axis=1)[:, :10])
    lo, hi = data.INT_RANGES[dtype]
    assert d2[0, 0] == 128 * (hi - lo) ** 2     # all-hi query, all-lo row
    assert got[0, 0] == 1 and got[1, 0] == 0    # each extreme finds its twin


@pytest.mark.parametrize("dtype", INTS)
def test_pool_reference_is_exact_on_integer_vectors(dtype):
    base, queries = _extreme_rows(dtype, n=200, q=4)
    pools = np.stack([base[:100], base[100:], base[:100], base[100:]])
    got = np.asarray(reference.pool_d2_l2(queries, pools))
    want = np.stack([_d2_int64(queries[i:i + 1], pools[i])[0]
                     for i in range(4)])
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.astype(np.int64), want)
    lo, hi = data.INT_RANGES[dtype]
    assert got[0, 0] == 128 * (hi - lo) ** 2 == 8_323_200


def test_float32_holds_uint8_distances_at_d128_and_refuses_beyond():
    """At d = 128, uint8's extremes keep every term of ``exact_knn``'s
    expansion below 2**24, and float32 computes it exactly; past
    d = 129 the check refuses."""
    d, m = 128, 255
    q = np.full(d, m, np.float32)
    for x in (np.zeros(d, np.float32), np.full(d, m, np.float32)):
        qq, qx, xx = (np.float32(np.dot(a, b)) for a, b in
                      ((q, q), (q, x), (x, x)))
        lead = np.float32(qq - np.float32(2) * qx)
        assert max(abs(int(qq)), abs(int(2 * qx)), abs(int(lead)),
                   int(lead + xx)) <= reference.int_bound(np.uint8, d)
        assert int(lead + xx) == int(((q.astype(np.int64) - x) ** 2).sum())
    assert reference.int_bound(np.uint8, d) == 2 * d * m * m == 16_646_400
    assert reference.int_bound(np.uint8, d) < reference.F32_EXACT
    assert reference.int_bound(np.int8, d) < reference.F32_EXACT
    reference.check_exact(np.uint8, 129)
    reference.check_exact(np.float32, 10 ** 6)    # floats are not refused
    with pytest.raises(ValueError, match="2\\*\\*24"):
        reference.exact_knn(np.zeros((4, 130), np.uint8),
                            np.zeros((1, 130), np.uint8), 2)


# ------------------------------------------------------------ recall rule
def _ties(dtype):
    """A query at the origin and four base rows tied at squared distance
    1, then rows at 4 and 9."""
    rows = np.array([[1, 0], [0, 1], [-1, 0], [0, -1], [2, 0], [3, 0]])
    shift = 1 if dtype == "uint8" else 0    # uint8 has no -1: shift both
    return (rows + shift).astype(dtype), np.full((1, 2), shift).astype(dtype)


@pytest.mark.parametrize("returned,want", [
    ([2, 3], 1.0),          # the other two of the tie at the k-th place
    ([0, 3], 1.0),
    ([3, 3], 0.5),          # an id counts once
    ([2, 4], 0.5),          # a farther row does not count
    ([-1, 2], 0.5)])        # nor a missing answer
@pytest.mark.parametrize("dtype", ["uint8", "int8"])
def test_integer_recall_counts_ties_at_the_kth_place(dtype, returned, want):
    base, query = _ties(dtype)
    gt = np.array([[0, 1]])             # one valid exact top-2
    got = reference.recall(base, query, np.array([0]), np.array([returned]),
                           gt, 2)
    assert got == want


def test_float_recall_is_the_id_set_count():
    base, query = _ties("float32")
    gt = np.array([[0, 1], [0, 1]])
    returned = np.array([[2, 3], [1, 0]])
    got = reference.recall(base, query, np.array([0, 0]), returned, gt, 2)
    assert got == 0.5                   # ties do not count on floats


def test_bf16_control_fails_a_uint8_launch():
    """bfloat16 holds every integer 0..256, so a uint8 pool's differences
    are exact in it; their squares are not, and the control misses."""
    ints = np.arange(257, dtype=np.float32)
    import jax.numpy as jnp
    assert np.array_equal(np.asarray(jnp.asarray(ints, jnp.bfloat16),
                                     np.float32), ints)
    sq = ints ** 2
    assert not np.array_equal(np.asarray(jnp.asarray(sq, jnp.bfloat16),
                                         np.float32), sq)
    base, queries = _extreme_rows("uint8", n=16 * 300, q=16)
    pools = base.reshape(16, 300, 128)
    ids = np.arange(16 * 300, dtype=np.int32).reshape(16, 300)
    ref_d2 = np.asarray(reference.pool_d2_l2(queries, pools))
    d, i = reference.control_l2_topk_masked(queries, pools, ids, k=10)
    assert reference.kernel_gap(ref_d2, ids, np.asarray(d),
                                np.asarray(i)) > 3 * L2_LIMIT


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("over,names", [
    ({"metric": "inner_product"}, "inner_product"),
    ({"dtype": "float16"}, "float16"),
    ({"storage": {"preset": "dfs", "n_shards": 4, "replicas": 1,
                  "erasure": "rs"}}, "erasure")],
    ids=["metric", "dtype", "storage"])
def test_deploy_refuses_before_any_work(monkeypatch, over, names):
    def no_work(*a, **kw):
        raise AssertionError("data generated before the refusal")
    monkeypatch.setattr(data, "vectors", no_work)
    with pytest.raises(ValueError, match=names):
        harness.deploy(_config("deep96-float", **over), data.sub_seeds(1))


def test_run_refuses_an_unsupported_metric_before_the_chip(monkeypatch,
                                                          capsys):
    import run
    cell = harness.load_cell("deep96-float.c64-L64")
    cell.config["metric"] = "inner_product"
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    with pytest.raises(ValueError, match="inner_product"):
        run.main(["--workload", cell.name, "--seed", str(2 ** 34),
                  "--seconds", "1", "--trace", "0"])
    assert "{" not in capsys.readouterr().out


# ------------------------------------------- integer deployments, served
@pytest.mark.parametrize("dtype", INTS)
def test_integer_vectors_reach_the_program_uncast(monkeypatch, dtype):
    """A tiny deployment stating an integer dtype: the build, the
    partition write and every submitted query get arrays of that dtype,
    and the run checks out correct against the exact references."""
    import repro.core.pag as pag_mod
    import repro.core.search as search_mod
    from repro.serving.engine import AnnsFrontend
    seen = []

    def spy(owner, attr, pos):
        orig = getattr(owner, attr)

        def call(*a, **kw):
            seen.append((attr, np.asarray(a[pos]).dtype))
            return orig(*a, **kw)
        monkeypatch.setattr(owner, attr, call)

    spy(pag_mod, "build_pag", 0)
    spy(search_mod, "write_partitions", 1)
    spy(AnnsFrontend, "submit", 1)
    cell = harness.load_cell("deep96-float.c64-L64")
    cell.config.update(TINY, dtype=dtype)
    seeds = data.sub_seeds(2 ** 33 + 9)
    dep = harness.deploy(cell.config, seeds)
    res = harness.run_deployed(cell, dep, seeds, 0.0, False,
                               time.perf_counter(), log=lambda s: None)
    assert {a for a, _ in seen} == {"build_pag", "write_partitions",
                                    "submit"}
    assert {t for _, t in seen} == {np.dtype(dtype)}
    assert res["correct"], res["checks"]
