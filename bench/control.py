#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --seeds 11,12,13 --seconds 30 \
        [--cells deep96-float.c64-L64]

For each seed, one process builds one deployment (where a cell scans PQ
codes, float residuals and PQ codes written together, so every cell
serves from it) and runs each cell's warm-up and window twice: once as
the program is (``program``: the lower readings), once with the bfloat16
reference in place of the scan kernels (``control``: the upper
readings). Each prints one JSON line with the numbers compared. The
benchmark's own runs never run this. Needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

CELLS = ("deep96-float.c64-L256", "deep96-pq.c64-L256")


@contextlib.contextmanager
def bf16_kernels():
    """The bfloat16 reference in the place of both scan kernels."""
    import reference
    from repro.kernels import ops
    orig = ops.l2_topk_masked, ops.pq_adc_masked
    ops.l2_topk_masked = reference.control_l2_topk_masked
    ops.pq_adc_masked = reference.control_pq_adc_masked
    try:
        yield
    finally:
        ops.l2_topk_masked, ops.pq_adc_masked = orig


VARIANTS = {"program": None, "control": bf16_kernels}


def readings(cells, seed: int, seconds, log=print) -> list:
    """One deployment for ``seed``; each cell under each variant."""
    import data
    import harness
    configs = [c.config for c in cells]
    keys = ("n", "d", "dtype", "n_queries", "query_noise", "vectors_seed",
            "index_seed", "build", "storage")
    if any(c[key] != configs[0][key] for c in configs for key in keys):
        raise ValueError("the cells do not share one deployment")
    seeds = data.sub_seeds(seed)
    pq_m = {c["plane"].get("pq_m") for c in configs} - {None}
    if pq_m:
        cfg = dict(configs[0], plane={"compression": "pq",
                                      "pq_m": pq_m.pop()})
        dep = harness.deploy(cfg, seeds, compression="pq")
    else:
        dep = harness.deploy(configs[0], seeds)
    out = []
    for cell in cells:
        for variant in VARIANTS:
            res = harness.run_deployed(cell, dep, seeds, seconds, False,
                                       time.perf_counter(),
                                       swap=VARIANTS[variant],
                                       log=lambda s: None)
            line = {"seed": seed, "cell": cell.name, "variant": variant,
                    "correct": res["correct"],
                    "checks": {k: v["value"]
                               for k, v in res["checks"].items()}}
            log(json.dumps(line))
            out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cells", default=",".join(CELLS),
                    help="cells that share one deployment, comma-separated")
    args = ap.parse_args(argv)
    import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cells = [harness.load_cell(c) for c in args.cells.split(",")]
    for seed in (int(s) for s in args.seeds.split(",")):
        readings(cells, seed, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
