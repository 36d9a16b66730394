"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def l2_topk_ref(q: jax.Array, x: jax.Array, k: int):
    """q [Q, d], x [N, d] -> (d2 [Q, k], ids [Q, k]) ascending."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    d2 = (jnp.sum(q * q, -1)[:, None] - 2 * q @ x.T
          + jnp.sum(x * x, -1)[None, :])
    d2 = jnp.maximum(d2, 0.0)
    neg, ids = jax.lax.top_k(-d2, k)
    return -neg, ids.astype(jnp.int32)


def l2_topk_masked_ref(q: jax.Array, pools: jax.Array, ids: jax.Array,
                       k: int):
    """q [Q, d]; pools [Q, C, d]; ids [Q, C] (-1 = padding) ->
    (d2 [Q, k], ids [Q, k]) ascending; short rows pad with (3.4e38, -1).
    Integer pools and queries are compared exactly: differences and
    squares in int32, the integer distance then in float32 (exact below
    2**24, so up to d = 129 for uint8)."""
    if jnp.issubdtype(pools.dtype, jnp.integer):
        diff = pools.astype(jnp.int32) - q.astype(jnp.int32)[:, None, :]
        d2 = jnp.sum(diff * diff, -1).astype(jnp.float32)
    else:
        q = q.astype(jnp.float32)
        pools = pools.astype(jnp.float32)
        d2 = (jnp.sum(q * q, -1)[:, None]
              - 2 * jnp.einsum("qd,qcd->qc", q, pools)
              + jnp.sum(pools * pools, -1))
        d2 = jnp.maximum(d2, 0.0)
    d2 = jnp.where(ids >= 0, d2, 3.4e38)
    c = pools.shape[1]
    if c < k:  # pad so top_k has k columns to select from
        d2 = jnp.pad(d2, ((0, 0), (0, k - c)), constant_values=3.4e38)
        ids = jnp.pad(ids, ((0, 0), (0, k - c)), constant_values=-1)
    neg, pos = jax.lax.top_k(-d2, k)
    out_i = jnp.take_along_axis(ids, pos, axis=1)
    out_d = jnp.where(out_i >= 0, -neg, 3.4e38)
    out_i = jnp.where(out_i >= 0, out_i, -1)
    return out_d, out_i


def pq_adc_ref(lut: jax.Array, codes: jax.Array):
    """lut [M, 256] f32, codes [N, M] int32 -> dists [N] f32."""
    m = lut.shape[0]
    return jnp.sum(lut[jnp.arange(m)[None, :], codes], axis=1)


def pq_adc_masked_ref(luts: jax.Array, codes: jax.Array, ids: jax.Array,
                      k: int):
    """luts [Q, M, 256]; codes [Q, C, M]; ids [Q, C] (-1 = padding) ->
    (d2 [Q, k], ids [Q, k]) ascending; short rows pad with (3.4e38, -1)."""
    codes = codes.astype(jnp.int32)
    d2 = jax.vmap(pq_adc_ref)(luts, codes)          # [Q, C]
    d2 = jnp.where(ids >= 0, d2, 3.4e38)
    c = codes.shape[1]
    if c < k:  # pad so top_k has k columns to select from
        d2 = jnp.pad(d2, ((0, 0), (0, k - c)), constant_values=3.4e38)
        ids = jnp.pad(ids, ((0, 0), (0, k - c)), constant_values=-1)
    neg, pos = jax.lax.top_k(-d2, k)
    out_i = jnp.take_along_axis(ids, pos, axis=1)
    out_d = jnp.where(out_i >= 0, -neg, 3.4e38)
    out_i = jnp.where(out_i >= 0, out_i, -1)
    return out_d, out_i


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True):
    """q [B, H, Sq, d]; k, v [B, H, Sk, d] -> [B, H, Sq, d]."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        qp = jnp.arange(sq)[:, None] + (sk - sq)
        kp = jnp.arange(sk)[None, :]
        s = jnp.where(kp <= qp, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
