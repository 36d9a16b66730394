"""The benchmark's inputs: the configuration's vectors, and orders drawn
from ``--seed``.

``clustered`` is a copy of the ``clustered`` mixture the program's own
tests use (``data/vectors.py``): Gaussian clusters with zipf-weighted
sizes and per-cluster anisotropic scales, and queries that are held-out
perturbations of base points. It is copied, not imported, so that no
change to the program can change what the benchmark feeds it.

The deployment is the configuration's own and the same in every run:
its vectors (``vectors_seed``), their row order, and the index built
over them (``index_seed``: the build's and the PQ training's random
choices). So every run builds the same index, at the same shapes, and
finds its build programs in the compile cache after the first run.
``--seed`` draws only what a run serves and checks: ``sub_seeds``
splits it (of any size: seeds may exceed 32 bits) into independent
32-bit seeds for the order of the micro-batches, the store's latency
draws and the sample of kernel launches that is checked. So every seed
gets the same vectors, queries and batches, in another order.

The vectors come in the configuration's ``dtype``. ``float32`` is the
mixture as drawn. ``uint8`` and ``int8`` map it onto the type's whole
range ``[lo, hi]`` ([0, 255] or [-128, 127]) by one affine map fixed by
the base alone: with ``b0``, ``b1`` the least and the greatest
coordinate of the float base,

    v -> clip(rint(lo + (v - b0) * (hi - lo) / (b1 - b0)), lo, hi)

taken in float64. The queries, perturbed in float as for ``float32``,
go through the same map, so a query beyond the base's range is clipped.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

PARTS = ("batches", "store", "sample")
INT_RANGES = {"uint8": (0, 255), "int8": (-128, 127)}
DTYPES = ("float32",) + tuple(INT_RANGES)


def sub_seeds(seed: int) -> Dict[str, int]:
    """One 32-bit seed per part of a run, all drawn from ``seed``."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    state = np.random.SeedSequence(seed).generate_state(len(PARTS))
    return {part: int(s) for part, s in zip(PARTS, state)}


def clustered(n: int, d: int, n_queries: int, seed: int,
              query_noise: float = 0.1) -> Tuple[np.ndarray, np.ndarray]:
    """Base [n, d] and queries [n_queries, d], float32."""
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


def vectors(config: dict) -> Tuple[np.ndarray, np.ndarray]:
    """The configuration's base and queries, in its ``dtype``."""
    dtype = config["dtype"]
    if dtype not in DTYPES:
        raise ValueError(f"dtype {dtype!r}: the benchmark generates "
                         f"{list(DTYPES)} only")
    base, queries = clustered(config["n"], config["d"], config["n_queries"],
                              config["vectors_seed"], config["query_noise"])
    if dtype == "float32":
        return base, queries
    lo, hi = INT_RANGES[dtype]
    b0, b1 = float(base.min()), float(base.max())
    scale = (hi - lo) / (b1 - b0)

    def to_int(v):
        x = lo + (v.astype(np.float64) - b0) * scale
        return np.clip(np.rint(x), lo, hi).astype(dtype)
    return to_int(base), to_int(queries)


def batch_order(n_batches: int, seed: int) -> np.ndarray:
    """The order in which a run's window cycles through the batches."""
    return np.random.default_rng(seed).permutation(n_batches)
