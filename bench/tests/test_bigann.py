"""The BIGANN cell (SIFT-shaped 128-d uint8 vectors) on the CPU at n=1,200:
a run as the program is comes out correct with every launch in uint8, the
bfloat16 control and a broken path do not, a launch widened on the way to
the kernel is refused or shows in the pool bytes, and the pool-bytes
reader reads what the captured launches imply."""
from __future__ import annotations

import contextlib
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import data  # noqa: E402
import harness  # noqa: E402

CELL = "bigann128-uint8.c64-L256"
TINY = {"n": 1200, "n_queries": 128}    # two micro-batches of 64
READER = "l2_pool_bytes_per_query"


@pytest.fixture(scope="module")
def tiny():
    cell = harness.load_cell(CELL)
    cell.config.update(TINY)
    seeds = data.sub_seeds(2 ** 33 + 17)
    return cell, harness.deploy(cell.config, seeds), seeds


def _run(tiny, swap=None):
    cell, dep, seeds = tiny
    return harness.run_deployed(cell, dep, seeds, 0.0, False,
                                time.perf_counter(), swap=swap,
                                log=lambda s: None)


@contextlib.contextmanager
def _swap_l2(make):
    from repro.kernels import ops
    orig = ops.l2_topk_masked
    ops.l2_topk_masked = make(orig)
    try:
        yield
    finally:
        ops.l2_topk_masked = orig


def recording(seen):
    """Each launch's query and pool dtypes, as the kernel gets them."""
    def make(orig):
        def launch(q, pools, ids, **kw):
            seen.append((np.dtype(q.dtype), np.dtype(pools.dtype)))
            return orig(q, pools, ids, **kw)
        return launch
    return _swap_l2(make)


def test_the_cell_is_bigann_at_its_published_widths():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (w,) = [w for w in spec["workloads"] if w["name"] == CELL]
    cell = harness.load_cell(CELL)
    cfg = cell.config
    assert w["chips"] == 1 and cell.traffic["name"] == "c64-L256"
    assert (cfg["d"], cfg["dtype"], cfg["metric"], cfg["k"]) == (
        cfg["published"]["d"], "uint8", "squared_l2", 10)
    assert cfg["plane"] == {"compression": "none"}
    assert set(cfg["reduced"]) == {"n", "n_queries"}
    (m,) = [m for m in spec["per_layer"] if m["name"] == READER]
    assert m["workloads"] == [CELL]


def test_sound_run_is_correct_and_integer_throughout(tiny):
    cell, dep, _ = tiny
    assert dep.base.dtype == dep.queries.dtype == np.uint8
    assert dep.serving.pag.pg.dtype == "uint8"
    kernel = []
    with recording(kernel):
        res = _run(tiny)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 64 and res["failed"] == 0
    assert set(kernel) == {(np.dtype(np.uint8), np.dtype(np.uint8))}
    checks = res["checks"]
    # exact integer distances: nothing to round
    assert checks["d2_gap"]["value"] == 0.0
    assert checks["l2_topk_masked_gap"]["value"] == 0.0
    assert checks["recall_at_10"]["limit"] == \
        cell.config["guarantees"]["recall_at_10_min"]


def test_bf16_control_is_not_correct(tiny):
    res = _run(tiny, swap=control.bf16_kernels)
    assert not res["correct"]
    for name in ("d2_gap", "l2_topk_masked_gap"):
        c = res["checks"][name]
        assert c["value"] > c["limit"], name


def float_pool():
    """The pool reaches the kernel widened to float32, the queries as
    they are: the kernel refuses the mixed launch."""
    def make(orig):
        def launch(q, pools, ids, **kw):
            return orig(q, np.asarray(pools, np.float32), ids, **kw)
        return launch
    return _swap_l2(make)


def altered_ids():
    """The scan's ids leave their distances (rolled per row)."""
    def make(orig):
        def launch(*a, **kw):
            d, i = orig(*a, **kw)
            return d, np.roll(np.asarray(i), 1, axis=1)
        return launch
    return _swap_l2(make)


def test_a_float32_pool_for_a_uint8_launch_is_refused(tiny):
    with pytest.raises(TypeError, match="float32 pools"):
        _run(tiny, swap=float_pool)


def test_ids_leaving_their_distances_are_not_correct(tiny):
    res = _run(tiny, swap=altered_ids)
    assert not res["correct"], res["checks"]


def test_a_launch_widened_on_the_host_reads_four_times_the_pool(tiny):
    """Widening both pools and queries to float32 before the kernel keeps
    the integer distances exact, so ``correct`` cannot tell it apart; the
    launch's recorded element width and the pool-bytes reader can."""
    from repro.dataplane import scan as scan_mod
    orig = scan_mod.ScanStage.topk

    def widened(self, queries, pool_ids, pool_vecs, k):
        return orig(self, queries.astype(np.float32), pool_ids,
                    [v.astype(np.float32) for v in pool_vecs], k)

    cell, dep, seeds = tiny
    read = {}
    for name, swap in (("uint8", None), ("float32", widened)):
        capture = harness.Capture(seeds["sample"])
        scan_mod.ScanStage.topk = swap or orig
        try:
            with capture.installed():
                win = harness.run_batches(
                    _frontend(tiny), dep.queries,
                    harness.batches_of(len(dep.queries), 64), None)
        finally:
            scan_mod.ScanStage.topk = orig
        read[name] = (harness.read_metric(READER, {
            "launches": capture.shapes, "window": win}),
            {s[4:] for s in capture.shapes["l2_topk_masked"]})
    assert read["uint8"][1] == {(1, 1)} and read["float32"][1] == {(4, 4)}
    assert read["float32"][0] == 4 * read["uint8"][0] > 0


def _frontend(tiny):
    from repro.serving.engine import AnnsFrontend
    cell, dep, _ = tiny
    return AnnsFrontend(dep.serving,
                        harness.search_config(cell.config, cell.traffic),
                        max_batch=cell.traffic["clients"])


def test_pool_bytes_reader_on_a_built_capture():
    capture = harness.Capture(0)
    capture.shapes["l2_topk_masked"] = [(64, 3072, 128, 10, 1, 1),
                                        (64, 2816, 128, 10, 1, 1)]
    win = types.SimpleNamespace(q_idx=np.arange(100))
    want = 64 * (3072 + 2816) * 128 / 100
    assert harness.read_metric(READER, {"launches": capture.shapes,
                                        "window": win}) == want
    capture.shapes["l2_topk_masked"] = [(64, 3072, 96, 10, 4, 4)]
    assert harness.read_metric(READER, {"launches": capture.shapes,
                                        "window": win}) == \
        4 * 64 * 3072 * 96 / 100
    capture.shapes["l2_topk_masked"] = []
    assert harness.read_metric(READER, {"launches": capture.shapes,
                                        "window": win}) is None
