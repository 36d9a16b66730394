"""Distance primitives. δ(·,·) is SQUARED Euclidean throughout, matching
the paper's notation (§II Table II). The TPU hot path (partition full-scan
= fused distance + top-k) is the Pallas `l2_topk` kernel; these jnp
implementations are its oracle and the CPU execution path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sq_norms(x: jax.Array) -> jax.Array:
    return jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1)


def cdist2(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared L2 distances [Q, N] = |q|^2 - 2 q.x + |x|^2 (MXU-friendly).

    The matmul runs at full f32 precision: a TPU's default would round
    the operands to bf16, which reorders near neighbours."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qx = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    d2 = sq_norms(q)[:, None] - 2.0 * qx + sq_norms(x)[None, :]
    return jnp.maximum(d2, 0.0)


def pairwise2(a: jax.Array, b: jax.Array) -> jax.Array:
    return cdist2(a, b)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_l2(q: jax.Array, x: jax.Array, k: int):
    """Exact top-k nearest (ids, sq-dists) of each query row against x."""
    d2 = cdist2(q, x)
    neg, idx = jax.lax.top_k(-d2, k)
    return idx, -neg
