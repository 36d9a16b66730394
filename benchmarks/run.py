"""Benchmark harness: one module per paper table/figure + roofline reader.

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable tables).
``--fast`` shrinks the dataset for smoke runs; the default matches the
numbers quoted in EXPERIMENTS.md.

Artifacts: every selected mode also writes ``BENCH_<mode>.json`` (rows as
typed dicts — schema in benchmarks/README.md) into ``--bench-dir``.
``--trace DIR`` runs each mode under a span tracer and dumps one Perfetto
``trace_<mode>.json`` per mode plus a per-batch timeline breakdown.
"""
from __future__ import annotations

import argparse
import os
import time

# mode -> "module:function"; imports stay lazy so one broken or heavy
# module (e.g. the LM step) never blocks the rest of the harness
MODES = {
    "build_time": "benchmarks.build_time:main",
    "qps_recall": "benchmarks.qps_recall:main",
    "pq": "benchmarks.qps_recall:pq_main",  # compressed-plane rows only
    "redundancy": "benchmarks.redundancy:main",
    "radius_grid": "benchmarks.radius_grid:main",
    "drs_tail": "benchmarks.drs_tail:main",
    "cache_effect": "benchmarks.cache_effect:main",
    "prefetch": "benchmarks.prefetch:main",
    "chaos": "benchmarks.chaos:main",
    "kernels": "benchmarks.kernels_micro:main",
    "lm": "benchmarks.lm_step:main",
    "roofline": "benchmarks.roofline:main",
}
# modes skipped without --all / --only (pq rides inside qps_recall)
DEFAULT_SKIP = ("pq",)


def _resolve(name: str):
    import importlib
    mod_name, fn_name = MODES[name].split(":")
    return getattr(importlib.import_module(mod_name), fn_name)


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--fast", action="store_true",
                    help="shrink datasets for a quick run")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: --fast sizes AND trimmed sweeps")
    ap.add_argument("--all", action="store_true",
                    help="run every registered benchmark mode")
    ap.add_argument("--only", default="",
                    help="comma list of modes: " + ",".join(MODES))
    ap.add_argument("--bench-dir", default=".",
                    help="directory for BENCH_<mode>.json artifacts")
    ap.add_argument("--trace", default="", metavar="DIR",
                    help="record spans; write DIR/trace_<mode>.json + "
                         "print per-batch timeline breakdowns")
    args = ap.parse_args()

    from benchmarks.common import (
        BenchContext,
        collect_rows,
        emit_bench_json,
    )
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    fast = args.fast or args.smoke
    ctx = BenchContext(n=6000 if fast else 12000,
                       n_queries=100 if fast else 200,
                       smoke=args.smoke)
    if args.all:
        selected = list(MODES)
    elif args.only:
        selected = [m.strip() for m in args.only.split(",") if m.strip()]
        unknown = [m for m in selected if m not in MODES]
        if unknown:
            ap.error(f"unknown mode(s) {unknown}; choose from "
                     + ",".join(MODES))
    else:
        selected = [m for m in MODES if m not in DEFAULT_SKIP]

    print("name,us_per_call,derived")
    t0 = time.time()
    for name in selected:
        fn = _resolve(name)
        if args.trace:
            from repro.obs import observe
            from repro.obs.report import timeline_breakdown
            from repro.obs.trace import Tracer
            tracer = Tracer()
            with collect_rows() as rows, observe(tracer=tracer):
                fn(ctx)
            os.makedirs(args.trace, exist_ok=True)
            path = tracer.save(os.path.join(args.trace,
                                            f"trace_{name}.json"))
            print(f"\n# trace: {path}")
            print(timeline_breakdown(tracer))
        else:
            with collect_rows() as rows:
                fn(ctx)
        emit_bench_json(name, rows, out_dir=args.bench_dir)
    print(f"\ntotal benchmark time: {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
