#!/usr/bin/env python3
"""Stages of the served path, read from the program's own host spans.

The program opens a ``jax.profiler.TraceAnnotation`` named ``anns/<stage>``
around each stage of a batch (``repro.obs.host_span``), with integer
stats: ``anns/frontend.flush`` (the batch's root), ``anns/search``,
``anns/graph``, ``anns/search.app_replay``, ``anns/plan.build``,
``anns/wave.*``, ``anns/search.pool``, ``anns/scan.*`` and
``anns/search.stats``. ``load`` reads them, with their stats, from the
``.xplane.pb`` of a traced window; the functions below reduce them, beside
the device operations of ``devtrace.Trace``, to per-stage numbers:

* ``idle_by_program``: chip 0's idle time in the window, split by the
  innermost ``anns/`` span over each instant, by overlap;
* ``STAGE_METRICS``: host milliseconds a batch per stage, bytes copied to
  the device a batch, the scan launches' share of filled slots, and the
  frontend's queue wait.

``devtrace.load`` keeps only the spans the benchmark wraps around the
program (``harness.SPANS``), so the benchmark's readers do not see these
yet. This file also runs them on the chip:

    python3 bench/stages.py --workload <cell> [--workload <cell>] \\
        --seed <n> [--seed <n> ...] --seconds <s> --out <file.jsonl>

deploys the cells' index once, then for each cell and seed runs one
traced window (``harness.run_deployed``), after an untraced one for the
first seed, and prints one JSON line per traced window: the traced and
untraced ``qps`` (the cost of tracing), the traced run's result, the
stage metrics, the idle split a batch and the batches' wall times.
Without a TPU it exits non-zero.
"""
from __future__ import annotations

import bisect
import contextlib
import json
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import devtrace  # noqa: E402

PREFIX = "anns/"
OUTSIDE = "outside program"

# (start_ns, end_ns, name, stats)
ProgramSpan = Tuple[float, float, str, Dict[str, int]]


def load(log_dir: Path, window: Tuple[float, float]) -> List[ProgramSpan]:
    """The ``anns/`` host events of the trace under ``log_dir``, with
    their stats, clipped to ``window`` and sorted by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(devtrace.find_xplane(log_dir)))
    out: List[ProgramSpan] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX) and e.end_ns > window[0] \
                        and e.start_ns < window[1]:
                    out.append((max(e.start_ns, window[0]),
                                min(e.end_ns, window[1]), e.name,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[0])


def _match(name: str, key: str) -> bool:
    """``key`` is a span name, or a prefix ending in ``*``."""
    return name.startswith(key[:-1]) if key.endswith("*") else name == key


def span_s(program: List[ProgramSpan], key: str) -> float:
    """Seconds covered by the spans that ``key`` names (the union)."""
    return devtrace._length(devtrace.union(
        s for s in program if _match(s[2], key))) / 1e9


def self_s(program: List[ProgramSpan], name: str) -> float:
    """Seconds of the spans called ``name`` less those covered by the
    ``anns/`` spans nested in them."""
    starts = [s[0] for s in program]
    tot = 0.0
    for s0, s1, n, _ in program:
        if n != name:
            continue
        i = bisect.bisect_left(starts, s0)
        kids = [c for c in program[i:bisect.bisect_right(starts, s1)]
                if c[1] <= s1 and c[:3] != (s0, s1, n)]
        tot += (s1 - s0) - devtrace._length(devtrace.union(kids))
    return tot / 1e9


def stat_sum(program: List[ProgramSpan], key: str, stat: str) -> int:
    """Sum of one stat over the spans that ``key`` names."""
    return sum(s[3].get(stat, 0) for s in program if _match(s[2], key))


def idle_by_program(tr: "devtrace.Trace",
                    program: List[ProgramSpan]) -> Dict[str, float]:
    """Chip 0's idle seconds in the window, by the innermost ``anns/``
    span over each instant (the shortest of those that cover it), split
    by overlap; idle time no span covers goes under ``OUTSIDE``. Empty
    where the trace holds no device operation."""
    if not tr.ops:
        return {}
    busy = devtrace.union(tr.ops[min(tr.ops)])
    gaps, cur = [], tr.window[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < tr.window[1]:
        gaps.append((cur, tr.window[1]))
    # elementary segments between span boundaries, each with its
    # innermost span
    bounds = sorted({t for s in program for t in s[:2]}
                    | {tr.window[0], tr.window[1]})
    by_start = sorted(program, key=lambda s: s[0])
    active: List[ProgramSpan] = []
    nxt = 0
    segs: List[Tuple[float, float, str]] = []
    for a, b in zip(bounds, bounds[1:]):
        while nxt < len(by_start) and by_start[nxt][0] <= a:
            active.append(by_start[nxt])
            nxt += 1
        active = [s for s in active if s[1] > a]
        name = min(active, key=lambda s: s[1] - s[0])[2] if active \
            else OUTSIDE
        segs.append((a, b, name))
    tot: Dict[str, float] = defaultdict(float)
    i = 0
    for g0, g1 in gaps:                   # both lists sorted
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            lo, hi = max(g0, segs[j][0]), min(g1, segs[j][1])
            if hi > lo:
                tot[segs[j][2]] += (hi - lo) / 1e9
            j += 1
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


# ---------------------------------------------------------------- metrics
def _per_batch_ms(tr, program, seconds: float) -> Optional[float]:
    if not program or not tr.n_batches:
        return None
    return seconds * 1e3 / tr.n_batches


def frontend_queue_wait_ms(tr, program):
    """ms a query waited in ``AnnsFrontend`` from submit to its flush."""
    n = stat_sum(program, PREFIX + "frontend.flush", "tickets")
    if not n:
        return None
    return stat_sum(program, PREFIX + "frontend.flush",
                    "queue_wait_ns_sum") / n / 1e6


def orchestrator_host_ms(tr, program):
    """Self time of ``search_pag`` and its pool and stats steps, a batch."""
    return _per_batch_ms(tr, program, sum(
        self_s(program, PREFIX + n)
        for n in ("search", "search.pool", "search.stats")))


def app_replay_host_ms(tr, program):
    """The APP replay and the fetch plans, a batch."""
    return _per_batch_ms(tr, program,
                         span_s(program, PREFIX + "search.app_replay")
                         + span_s(program, PREFIX + "plan.build"))


def storage_wave_host_ms(tr, program):
    """The simulated storage waves and their clock charges, a batch."""
    return _per_batch_ms(tr, program, span_s(program, PREFIX + "wave.*"))


def h2d_mb_per_batch(tr, program):
    """MB copied host to device a batch (graph, queries, pool pads)."""
    if not program or not tr.n_batches:
        return None
    return stat_sum(program, PREFIX + "*", "h2d_bytes") / 1e6 / tr.n_batches


def _fill_pct(program, launch: str) -> Optional[float]:
    slots = stat_sum(program, PREFIX + launch, "slots")
    if not slots:
        return None
    return 100.0 * stat_sum(program, PREFIX + launch, "filled") / slots


def l2_topk_masked_fill_pct(tr, program):
    """Share of the exact scan's launch slots that hold a candidate."""
    return _fill_pct(program, "scan.topk_launch")


def pq_adc_masked_fill_pct(tr, program):
    """Share of the ADC launch's slots that hold a candidate."""
    return _fill_pct(program, "scan.adc_launch")


STAGE_METRICS: Dict[str, Callable] = {
    f.__name__: f for f in (
        frontend_queue_wait_ms, orchestrator_host_ms, app_replay_host_ms,
        storage_wave_host_ms, h2d_mb_per_batch, l2_topk_masked_fill_pct,
        pq_adc_masked_fill_pct)}


def stage_sum_ms(tr, program) -> Optional[float]:
    """Host ms a batch over the stages: the graph phase, the
    orchestrator's self time, APP replay and plans, waves and scan
    steps. Beside the batch's wall time it shows what no stage holds."""
    if not program or not tr.n_batches:
        return None
    graph = span_s(program, PREFIX + "graph") * 1e3 / tr.n_batches
    scan = span_s(program, PREFIX + "scan.*") * 1e3 / tr.n_batches
    return graph + scan + sum(
        STAGE_METRICS[m](tr, program) for m in (
            "orchestrator_host_ms", "app_replay_host_ms",
            "storage_wave_host_ms"))


def read(tr, program) -> dict:
    """Every stage number of one traced window."""
    idle = idle_by_program(tr, program)
    nb = max(tr.n_batches, 1)
    idle_s = sum(idle.values())
    # idle no stage holds: outside every span, or in the self time of
    # the two spans that only enclose stages
    loose = sum(idle.get(k, 0.0) for k in (
        OUTSIDE, PREFIX + "frontend.flush", PREFIX + "search"))
    return {
        "metrics": {k: f(tr, program) for k, f in STAGE_METRICS.items()},
        "stage_sum_ms": stage_sum_ms(tr, program),
        "spans_per_batch": len(program) / nb,
        "idle_ms_per_batch": {k: v * 1e3 / nb for k, v in idle.items()},
        "idle_s": idle_s,
        "unstaged_idle_pct": 100.0 * loose / idle_s if idle_s else None,
    }


# ------------------------------------------------------------- chip runs
@contextlib.contextmanager
def keep_program(into: dict):
    """While installed, each ``devtrace.load`` of a traced run also keeps
    its ``Trace`` and program spans in ``into`` (the harness removes the
    trace's directory right after loading it)."""
    orig = devtrace.load

    def load_both(log_dir, span_names):
        tr = orig(log_dir, span_names)
        into["trace"], into["program"] = tr, load(log_dir, tr.window)
        return tr

    devtrace.load = load_both
    try:
        yield into
    finally:
        devtrace.load = orig


def span_cost_ns(n: int = 100_000) -> Dict[str, float]:
    """ns per ``host_span`` enter and exit with no trace active, with no
    stats and with two."""
    from repro.obs import host_span
    out = {}
    for label, stats in (("no_stats", {}), ("two_stats",
                                            {"gets": 1, "bytes": 2})):
        t0 = time.perf_counter()
        for _ in range(n):
            with host_span("cost", **stats):
                pass
        out[label] = (time.perf_counter() - t0) / n * 1e9
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    import harness
    import data
    import costs
    cells = [harness.load_cell(w) for w in args.workload]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"stages: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    peaks = costs.peaks(devices[0].device_kind)
    harness.enable_compile_cache()
    args.out.parent.mkdir(parents=True, exist_ok=True)

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        with args.out.open("a") as f:
            f.write(line + "\n")

    emit({"phase": "span_cost_ns", **span_cost_ns(),
          "device": devices[0].device_kind})
    # one deployment serves every cell: "pq" writes float residuals and
    # PQ codes, and the cells share data, build and storage
    pq = [c.config for c in cells if c.config["plane"]["compression"] == "pq"]
    dep = harness.deploy((pq or [cells[0].config])[0],
                         data.sub_seeds(args.seed[0]),
                         compression="pq" if pq else None)
    emit({"phase": "deploy", **dep.timings})
    for cell in cells:
        for seed in args.seed:
            seeds = data.sub_seeds(seed)
            logs: List[dict] = []

            def log(s):
                logs.append(json.loads(s))

            plain = None
            if seed == args.seed[0]:
                plain = harness.run_deployed(cell, dep, seeds, args.seconds,
                                             False, time.perf_counter(),
                                             peaks=peaks, log=log)
            with keep_program({}) as got:
                traced = harness.run_deployed(cell, dep, seeds,
                                              args.seconds, True,
                                              time.perf_counter(),
                                              peaks=peaks, log=log)
            tr, program = got["trace"], got["program"]
            win = [x for x in logs if x["phase"] == "window"][-1]
            emit({"cell": cell.name, "seed": seed,
                  "untraced_qps": plain and plain["metrics"]["qps"]["value"],
                  "traced_qps": win["queries"] / win["s"],
                  "correct": traced["correct"]
                  and (plain is None or plain["correct"]),
                  "batches": tr.n_batches, "window_s": tr.window_s,
                  "busy_s": tr.busy_s(),
                  "batch_s_median": statistics.median(win["batch_s"]),
                  "per_layer": {k: v["value"] for k, v in
                                traced["metrics"].items()},
                  **read(tr, program)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
