"""The chip benchmark's own pieces, on the CPU at small sizes: data,
costs, references, the trace reduction, the file layout, and whole runs
(less the look for a chip) that must come out correct as the program is
and not correct under the bfloat16 control or with the served path
broken underneath."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import costs  # noqa: E402
import data  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

FLOAT, PQ = "deep96-float.c64-L256", "deep96-pq.c64-L256"
LOW = "deep96-float.c64-L64"    # the float plane at low search effort
TINY = {"n": 1200, "n_queries": 128}    # two micro-batches of 64


# ------------------------------------------------------------- data, seeds
def test_generator_is_deterministic_for_a_seed():
    seed = 2 ** 40 + 3
    a, b = data.clustered(500, 96, 16, seed), data.clustered(500, 96, 16,
                                                              seed)
    c = data.clustered(500, 96, 16, seed + 1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.float32 and a[1].shape == (16, 96)


def test_generator_matches_the_program_mixture():
    from repro.data.vectors import make_dataset
    ds = make_dataset("clustered", n=500, d=96, n_queries=16, k_gt=10,
                      seed=7)
    base, queries = data.clustered(500, 96, 16, 7)
    np.testing.assert_array_equal(base, ds.base)
    np.testing.assert_array_equal(queries, ds.queries)


def test_every_seed_gets_the_same_vectors_in_another_order():
    config = {"n": 300, "d": 96, "n_queries": 8, "query_noise": 0.1,
              "vectors_seed": 3, "dtype": "float32"}
    base_a, q_a = data.vectors(config)
    base_b, q_b = data.vectors(config)
    np.testing.assert_array_equal(base_a, base_b)
    np.testing.assert_array_equal(q_a, q_b)
    a = data.batch_order(16, data.sub_seeds(11)["batches"])
    b = data.batch_order(16, data.sub_seeds(2 ** 36)["batches"])
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))
    np.testing.assert_array_equal(
        a, data.batch_order(16, data.sub_seeds(11)["batches"]))


def test_every_seed_builds_the_same_index():
    config = dict(harness.load_cell(FLOAT).config, n=600, n_queries=8)
    deps = [harness.deploy(config, data.sub_seeds(s)) for s in (7, 2 ** 35)]
    a, b = (d.serving.pag for d in deps)
    assert deps[0].timings["graph_rows"] == deps[1].timings["graph_rows"]
    assert a.n_parts == b.n_parts
    np.testing.assert_array_equal(a.plist, b.plist)
    np.testing.assert_array_equal(np.asarray(a.pg.nbrs), np.asarray(b.pg.nbrs))


def test_sub_seeds_take_seeds_beyond_32_bits():
    s = data.sub_seeds(2 ** 33 + 11)
    assert s == data.sub_seeds(2 ** 33 + 11)
    assert set(s) == set(data.PARTS)
    assert len(set(s.values())) == len(s)
    assert all(0 <= v < 2 ** 32 for v in s.values())
    with pytest.raises(ValueError):
        data.sub_seeds(-1)


# ------------------------------------------------------------ references
def test_exact_knn_matches_numpy_brute_force():
    rng = np.random.default_rng(0)
    base = rng.standard_normal((700, 96)).astype(np.float32)
    queries = rng.standard_normal((150, 96)).astype(np.float32)
    got = reference.exact_knn(base, queries, 10)
    d2 = ((queries[:, None, :].astype(np.float64) - base[None]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1)[:, :10]
    np.testing.assert_array_equal(got, want)


def test_answer_d2_is_the_distance_of_the_named_id():
    rng = np.random.default_rng(1)
    base = rng.standard_normal((50, 8)).astype(np.float32)
    queries = rng.standard_normal((3, 8)).astype(np.float32)
    ids = np.array([[4, 7], [0, 49], [3, 3]])
    got = reference.answer_d2(base, queries, np.array([2, 0, 1]), ids)
    assert got[1, 1] == pytest.approx(
        float(((queries[0] - base[49]).astype(np.float64) ** 2).sum()))


def _launch(seed=2, q=8, c=40, d=16, k=5):
    rng = np.random.default_rng(seed)
    qs = rng.standard_normal((q, d)).astype(np.float32)
    pools = rng.standard_normal((q, c, d)).astype(np.float32)
    ids = np.arange(q * c, dtype=np.int32).reshape(q, c)
    ids[:, c - 7:] = -1                   # ragged padding
    return qs, pools, ids, k


def test_kernel_gap_is_zero_for_the_reference_and_catches_faults():
    qs, pools, ids, k = _launch()
    ref_d2 = np.asarray(reference.pool_d2_l2(qs, pools))
    d, i = (np.asarray(a) for a in reference._topk_masked(ref_d2, ids, k))
    assert reference.kernel_gap(ref_d2, ids, d, i) == 0.0
    wrong = i.copy()
    wrong[0, 0] = 10 ** 6                 # not in the pool
    assert reference.kernel_gap(ref_d2, ids, d, wrong) == float("inf")
    rolled = np.roll(i, 1, axis=1)        # ids no longer match distances
    assert reference.kernel_gap(ref_d2, ids, d, rolled) > 1e-2
    worse = i.copy()                      # a farther candidate kept
    worse[0, k - 1] = ids[0, np.argsort(ref_d2[0, :33])[k + 3]]
    assert reference.kernel_gap(ref_d2, ids, d, worse) > 1e-3


def test_bf16_control_misses_the_float32_reference():
    qs, pools, ids, k = _launch(q=16, c=300, d=96, k=10)
    ref_d2 = np.asarray(reference.pool_d2_l2(qs, pools))
    d, i = reference.control_l2_topk_masked(qs, pools, ids, k=k)
    assert reference.kernel_gap(ref_d2, ids, np.asarray(d),
                                np.asarray(i)) > 1e-3


# ------------------------------------------------------------------ costs
def test_costs_at_fixed_shapes():
    assert costs.l2_topk_masked(64, 3072, 96, 10) == (56623104, 76313600)
    assert costs.pq_adc_masked(64, 3072, 16, 32) == (3145728, 4997120)
    peak = costs.peaks("TPU v5 lite")
    t, bound = costs.least_time(*costs.l2_topk_masked(64, 3072, 96, 10),
                                peak)
    assert bound == "bytes" and t == pytest.approx(76313600 / 819e9)
    assert costs.least_time(10 ** 15, 1, peak)[1] == "flops"
    with pytest.raises(KeyError):
        costs.peaks("cpu")


# ------------------------------------------------------------------ trace
def test_trace_reduction_of_built_intervals():
    ms = 1e6
    spans = [(0, 100 * ms, devtrace.WINDOW), (0, 100 * ms, devtrace.BATCH),
             (5 * ms, 35 * ms, "ScanStage.topk"),
             (40 * ms, 70 * ms, "ShardedServing.search")]
    tr = devtrace.Trace(
        window=(0, 100 * ms),
        ops={0: [(10 * ms, 20 * ms, "a"), (15 * ms, 30 * ms, "b"),
                 (50 * ms, 60 * ms, "a")]},
        modules={0: [(10 * ms, 30 * ms, "jit_l2_topk_masked(1)"),
                     (50 * ms, 60 * ms, "jit_greedy_search(2)")]},
        spans=spans)
    assert tr.window_s == pytest.approx(0.1)
    assert tr.busy_s() == pytest.approx(0.03)
    assert tr.n_batches == 1
    assert tr.module_s("jit_greedy_search") == pytest.approx(0.01)
    assert tr.module_s_within("jit_l2_topk_masked", "ScanStage.topk") \
        == pytest.approx(0.02)
    assert tr.span_s("ScanStage.topk") == pytest.approx(0.03)
    assert tr.top_ops() == [["a", pytest.approx(0.02)],
                            ["b", pytest.approx(0.015)]]
    idle = dict((k, v) for k, v in tr.idle_by_host())
    assert idle == {devtrace.BATCH: pytest.approx(0.04),
                    "ShardedServing.search": pytest.approx(0.02),
                    "ScanStage.topk": pytest.approx(0.01)}
    ctx = {"trace": tr, "window": None, "peaks": None,
           "launches": {"l2_topk_masked": []}}
    assert harness.read_metric("device_idle_pct", ctx) == pytest.approx(70)
    assert harness.read_metric("scan_host_ms", ctx) == pytest.approx(10)
    assert harness.read_metric("l2_topk_masked_roofline", ctx) is None


def test_trace_reduction_of_a_cpu_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation(devtrace.BATCH):
                    with jax.profiler.TraceAnnotation("ScanStage.topk"):
                        f(x).block_until_ready()
                    time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    tr = devtrace.load(tmp_path, {"ScanStage.topk"})
    assert tr.n_batches == 3
    assert tr.window_s >= 0.015
    assert 0 < tr.span_s("ScanStage.topk") < tr.window_s
    assert tr.busy_s() is None            # the CPU has no device plane
    ctx = {"trace": tr}
    assert harness.read_metric("device_idle_pct", ctx) is None
    assert harness.read_metric("graph_search_device_ms", ctx) is None
    assert harness.read_metric("scan_host_ms", ctx) > 0


# ------------------------------------------------------------ file layout
def test_workload_files_name_configs_and_traffic_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    files = sorted((BENCH / "workloads").glob("*.json"))
    assert {f.stem for f in files} == set(cells)
    for f in files:
        w = json.loads(f.read_text())
        assert (BENCH / "configs" / f"{w['config']}.json").is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (w["config"], w["traffic"], w["chips"]) == (
            cells[f.stem]["config"], cells[f.stem]["traffic"],
            cells[f.stem]["chips"])


def test_every_metric_has_a_reader_and_every_config_its_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(key in cfg for key in c["reduced"])


def test_run_refuses_without_a_tpu(capsys):
    import run
    assert run.main(["--workload", FLOAT, "--seed", str(2 ** 35),
                     "--seconds", "1", "--trace", "0"]) != 0
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())


# ------------------------------------------------- whole runs, tiny sizes
@pytest.fixture(scope="module")
def tiny():
    cells = {}
    for name in (FLOAT, PQ, LOW):
        cells[name] = harness.load_cell(name)
        cells[name].config.update(TINY)
    seeds = data.sub_seeds(2 ** 33 + 5)
    dep = harness.deploy(cells[PQ].config, seeds, compression="pq")
    return cells, dep, seeds


def _run(tiny, name, swap=None):
    cells, dep, seeds = tiny
    return harness.run_deployed(cells[name], dep, seeds, 0.0, False,
                                time.perf_counter(), swap=swap,
                                log=lambda s: None)


@pytest.mark.parametrize("name", [FLOAT, PQ, LOW])
def test_sound_run_is_correct(tiny, name):
    res = _run(tiny, name)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 64 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"qps", "latency_p95_ms", "recall_at_10",
                                   "bytes_per_query", "setup_s"}
    kernels = {FLOAT: {"l2_topk_masked_gap"}, LOW: {"l2_topk_masked_gap"},
               PQ: {"l2_topk_masked_gap", "pq_adc_masked_gap"}}[name]
    assert kernels <= set(res["checks"])
    cell = tiny[0][name]
    floor = res["checks"]["recall_at_10"]["limit"]
    if name == LOW:     # the traffic mix's own floor, not the config's
        assert floor == cell.traffic["recall_at_10_min"] \
            < cell.config["guarantees"]["recall_at_10_min"]
    else:
        assert "recall_at_10_min" not in cell.traffic
        assert floor == cell.config["guarantees"]["recall_at_10_min"]


def test_a_traffic_floor_replaces_the_config_floor_for_its_cells_only():
    config = {"guarantees": {"recall_at_10_min": 0.7}}
    assert harness.recall_floor(config, {"recall_at_10_min": 0.3}) == 0.3
    assert harness.recall_floor(config, {"clients": 64}) == 0.7
    assert config == {"guarantees": {"recall_at_10_min": 0.7}}
    with pytest.raises(ValueError, match="burst"):
        harness.run_deployed(
            dataclasses.replace(harness.load_cell(LOW),
                                traffic={"name": "x", "arrivals": "closed",
                                         "burst": 4}),
            None, data.sub_seeds(1), 0.0, False, 0.0)


@pytest.mark.parametrize("name,fails", [(FLOAT, "d2_gap"),
                                        (PQ, "pq_adc_masked_gap"),
                                        (LOW, "d2_gap")])
def test_bf16_control_is_not_correct(tiny, name, fails):
    res = _run(tiny, name, swap=control.bf16_kernels)
    assert not res["correct"]
    c = res["checks"][fails]
    assert c["value"] > c["limit"]


@contextlib.contextmanager
def _patch(owner, attr, make):
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def stale_answers():
    """Each search returns the answers of the call before it."""
    from repro.core.distributed import ShardedServing
    prev = []

    def make(orig):
        def search(self, queries, cfg, **kw):
            out = orig(self, queries, cfg, **kw)
            prev.append(out)
            return prev[-2] if len(prev) > 1 else out
        return search
    return _patch(ShardedServing, "search", make)


def half_batch():
    """Only the first half of each batch is answered."""
    from repro.core.distributed import ShardedServing

    def make(orig):
        def search(self, queries, cfg, **kw):
            ids, d2, stats = orig(self, queries, cfg, **kw)
            ids, d2 = ids.copy(), d2.copy()
            ids[len(ids) // 2:] = -1
            d2[len(d2) // 2:] = reference.INF
            return ids, d2, stats
        return search
    return _patch(ShardedServing, "search", make)


def altered_l2():
    """The exact scan's ids leave their distances (rolled per row)."""
    from repro.kernels import ops

    def make(orig):
        def launch(*a, **kw):
            d, i = orig(*a, **kw)
            return d, np.roll(np.asarray(i), 1, axis=1)
        return launch
    return _patch(ops, "l2_topk_masked", make)


def altered_pq():
    """The ADC scan names other candidates than those it scored."""
    from repro.kernels import ops

    def make(orig):
        def launch(*a, **kw):
            d, i = orig(*a, **kw)
            i = np.asarray(i)
            return d, np.where(i >= 0, (i + 1) % a[1].shape[1], i)
        return launch
    return _patch(ops, "pq_adc_masked", make)


@pytest.mark.parametrize("name,fault", [
    (FLOAT, stale_answers), (FLOAT, half_batch), (FLOAT, altered_l2),
    (PQ, altered_pq), (LOW, stale_answers), (LOW, half_batch),
    (LOW, altered_l2)], ids=["stale", "half_batch", "altered_l2",
                             "altered_pq", "stale-L64", "half_batch-L64",
                             "altered_l2-L64"])
def test_broken_path_is_not_correct(tiny, name, fault):
    res = _run(tiny, name, swap=fault)
    assert not res["correct"], res["checks"]
