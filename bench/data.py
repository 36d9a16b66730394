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
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

PARTS = ("batches", "store", "sample")


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
    """The configuration's base and queries."""
    return clustered(config["n"], config["d"], config["n_queries"],
                     config["vectors_seed"], config["query_noise"])


def batch_order(n_batches: int, seed: int) -> np.ndarray:
    """The order in which a run's window cycles through the batches."""
    return np.random.default_rng(seed).permutation(n_batches)
