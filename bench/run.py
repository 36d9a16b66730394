#!/usr/bin/env python3
"""Chip benchmark of the served ANN search path: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for (``bench/workloads/<cell>.json``). Earlier lines of
standard output report the set-up and the window (with the number of
programs compiled inside it, which should be 0); the numbers compared
for ``correct`` follow on standard error, each beside its limit; the last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics and a ``breakdown``), ``device`` and
``checks``. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result; so it does, naming what it refuses,
for a configuration that states a metric, dtype or storage setting that
the harness does not honour.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up runs from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    harness.refuse_unhonoured(cell.config)    # before the chip is touched
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    import costs
    peaks = costs.peaks(devices[0].device_kind)
    harness.enable_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, peaks=peaks)
    for name, c in result["checks"].items():
        side = "<=" if c["bound"] == "max" else ">="
        print(f"check {name}: {c['value']!r} {side} {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
