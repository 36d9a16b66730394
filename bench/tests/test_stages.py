"""The program's own host spans (``anns/...``) on the CPU: a traced run of
each plane through the benchmark's harness records every stage, nested
in its batch's ``anns/frontend.flush``, with the launch stats the launch
shapes imply; ``stages.py`` reduces them, and leaves the benchmark's
trace reduction as it was."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import data  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402
import stages  # noqa: E402

FLOAT, PQ = "deep96-float.c64-L256", "deep96-pq.c64-L256"
TINY = {"n": 1200, "n_queries": 128}    # two micro-batches of 64

COMMON = ["frontend.flush", "search", "graph", "search.app_replay",
          "plan.build", "wave.probe", "search.pool", "scan.topk_pad",
          "scan.topk_launch", "search.stats"]
PQ_ONLY = ["wave.codebook", "wave.refine", "scan.adc_pool", "scan.adc_lut",
           "scan.adc_launch", "scan.cover_select"]
EXISTING = ["device_idle_pct", "graph_search_device_ms", "scan_host_ms",
            "l2_topk_masked_roofline", "pq_adc_masked_roofline"]


class _KeptCapture(harness.Capture):
    """Keeps every launch of the window, and itself in ``made``."""
    made: list = []

    def __init__(self, seed):
        super().__init__(seed, keep=10 ** 6)
        _KeptCapture.made.append(self)


@pytest.fixture(scope="module")
def traced():
    """One traced window (one batch) of each plane on a tiny deployment:
    {cell: (Trace, program spans, Capture of the window's launches)}."""
    cells = {}
    for name in (FLOAT, PQ):
        cells[name] = harness.load_cell(name)
        cells[name].config.update(TINY)
    seeds = data.sub_seeds(2 ** 33 + 7)
    dep = harness.deploy(cells[PQ].config, seeds, compression="pq")
    out = {}
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "Capture", _KeptCapture)
    try:
        for name, cell in cells.items():
            with stages.keep_program({}) as got:
                res = harness.run_deployed(cell, dep, seeds, 0.0, True,
                                           time.perf_counter(),
                                           log=lambda s: None)
            assert res["correct"], res["checks"]
            out[name] = (got["trace"], got["program"],
                         _KeptCapture.made[-1])
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("name", [FLOAT, PQ])
def test_every_stage_span_nests_in_its_flush(traced, name):
    tr, program, _ = traced[name]
    want = COMMON + (PQ_ONLY if name == PQ else [])
    names = {s[2] for s in program}
    assert names == {stages.PREFIX + n for n in want}
    flushes = [s for s in program if s[2] == "anns/frontend.flush"]
    assert len(flushes) == tr.n_batches == 1
    (f0, f1, _, fstats), = flushes
    assert fstats["tickets"] == 64 and fstats["queue_wait_ns_sum"] > 0
    for s0, s1, n, _ in program:
        assert f0 <= s0 <= s1 <= f1, n
    once = [n for n in want if n not in ("plan.build", "wave.probe",
                                         "wave.refine", "wave.codebook")]
    for n in once:
        assert sum(s[2] == stages.PREFIX + n for s in program) == 1, n


@pytest.mark.parametrize("name,span,kernel", [
    (FLOAT, "scan.topk_launch", "l2_topk_masked"),
    (PQ, "scan.topk_launch", "l2_topk_masked"),
    (PQ, "scan.adc_launch", "pq_adc_masked")])
def test_launch_stats_match_the_launch_shapes(traced, name, span, kernel):
    _, program, capture = traced[name]
    (stats,) = [s[3] for s in program if s[2] == stages.PREFIX + span]
    (shape,) = capture.shapes[kernel]
    (args, _, _), = capture.sample[kernel]
    rows, width, inner, *_ = shape
    assert stats["slots"] == rows * width
    assert stats["filled"] == int((np.asarray(args[2]) >= 0).sum())
    if kernel == "l2_topk_masked":        # queries, pool vectors, ids
        want = 4 * rows * inner + 4 * rows * width * inner + 4 * rows * width
    else:                                 # LUTs, uint8 codes, positions
        want = 4 * rows * inner * 256 + rows * width * inner \
            + 4 * rows * width
    assert stats["h2d_bytes"] == want


@pytest.mark.parametrize("name", [FLOAT, PQ])
def test_stage_metrics_read_the_traced_run(traced, name):
    tr, program, _ = traced[name]
    got = {k: f(tr, program) for k, f in stages.STAGE_METRICS.items()}
    for k, v in got.items():
        if k == "pq_adc_masked_fill_pct" and name == FLOAT:
            assert v is None
        else:
            assert v is not None and np.isfinite(v), k
    assert 0 < got["l2_topk_masked_fill_pct"] <= 100
    graph = sum(s[3]["h2d_bytes"] for s in program
                if s[2] == "anns/graph")
    assert got["h2d_mb_per_batch"] * 1e6 > graph > 0
    assert stages.stage_sum_ms(tr, program) > 0
    assert stages.idle_by_program(tr, program) == {}   # no device plane


@pytest.mark.parametrize("name", [FLOAT, PQ])
def test_benchmark_reduction_ignores_the_program_spans(traced, name):
    """``devtrace.load`` keeps none of the program's spans, and the
    existing readers, ``idle_by_host`` and ``top_ops`` read the same
    before and after the stage reduction ran on the same trace."""
    tr, program, _ = traced[name]
    kept = {devtrace.WINDOW, devtrace.BATCH} | {a for _, a in harness.SPANS}
    assert {s[2] for s in tr.spans} <= kept
    ctx = {"trace": tr, "peaks": None,
           "launches": {"l2_topk_masked": [], "pq_adc_masked": []}}
    before = ([harness.read_metric(m, ctx) for m in EXISTING],
              tr.idle_by_host(), tr.top_ops())
    stages.read(tr, program)
    assert before == ([harness.read_metric(m, ctx) for m in EXISTING],
                      tr.idle_by_host(), tr.top_ops())


def _built(spans, ops):
    ms = 1e6
    return (devtrace.Trace(window=(0, 100 * ms), ops={0: ops}, modules={},
                           spans=[(0, 100 * ms, devtrace.WINDOW),
                                  (0, 100 * ms, devtrace.BATCH)]),
            sorted(spans, key=lambda s: s[0]))


def test_idle_is_split_by_overlap_between_the_innermost_spans():
    ms = 1e6
    # device busy 0-10 and 90-100; one 80 ms gap shared by six spans
    tr, program = _built(
        [(0, 100 * ms, "anns/frontend.flush", {"tickets": 4,
                                               "queue_wait_ns_sum": 8e6}),
         (5 * ms, 95 * ms, "anns/search", {}),
         (5 * ms, 30 * ms, "anns/graph", {"h2d_bytes": 2_000_000}),
         (30 * ms, 50 * ms, "anns/search.app_replay", {}),
         (50 * ms, 60 * ms, "anns/plan.build", {"keys": 3}),
         (60 * ms, 80 * ms, "anns/wave.probe", {"gets": 3}),
         (85 * ms, 92 * ms, "anns/scan.topk_launch",
          {"slots": 8, "filled": 6, "h2d_bytes": 1_000_000})],
        [(0, 10 * ms, "a"), (90 * ms, 100 * ms, "b")])
    idle = stages.idle_by_program(tr, program)
    assert idle == {"anns/graph": pytest.approx(0.02),
                    "anns/search.app_replay": pytest.approx(0.02),
                    "anns/wave.probe": pytest.approx(0.02),
                    "anns/plan.build": pytest.approx(0.01),
                    "anns/search": pytest.approx(0.005),
                    "anns/scan.topk_launch": pytest.approx(0.005)}
    assert sum(idle.values()) == pytest.approx(0.08)
    # 90 ms less the 82 ms its nested stages cover
    assert stages.self_s(program, "anns/search") == pytest.approx(0.008)
    assert stages.span_s(program, "anns/wave.*") == pytest.approx(0.02)
    m = {k: f(tr, program) for k, f in stages.STAGE_METRICS.items()}
    assert m["frontend_queue_wait_ms"] == pytest.approx(2.0)
    assert m["orchestrator_host_ms"] == pytest.approx(8.0)
    assert m["app_replay_host_ms"] == pytest.approx(30.0)
    assert m["storage_wave_host_ms"] == pytest.approx(20.0)
    assert m["h2d_mb_per_batch"] == pytest.approx(3.0)
    assert m["l2_topk_masked_fill_pct"] == pytest.approx(75.0)
    assert m["pq_adc_masked_fill_pct"] is None
    # a gap no span covers
    tr2, _ = _built([], [(0, 10 * ms, "a")])
    assert stages.idle_by_program(tr2, []) == {
        stages.OUTSIDE: pytest.approx(0.09)}


def test_stage_metrics_are_silent_without_program_spans():
    ms = 1e6
    tr, _ = _built([], [(0, 10 * ms, "a")])
    assert all(f(tr, []) is None for f in stages.STAGE_METRICS.values())
    assert stages.stage_sum_ms(tr, []) is None
