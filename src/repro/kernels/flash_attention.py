"""Flash attention (Pallas TPU): online-softmax tiles resident in VMEM.

The serving-stack prefill hot spot. The jnp chunked path in
models/attention.py stages per-chunk score tiles through HBM (the
dominant memory-roofline term of the train/prefill cells — see
EXPERIMENTS.md §Perf); this kernel keeps the (m, l, acc) state and score
tiles in VMEM across the kv-block grid dimension.

Grid: (q_blocks, kv_blocks); kv innermost so the running state carries
across kv steps for one q tile, then finalizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            sq: int, sk: int):
    qi = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale        # [BQ, d]
    k = k_ref[...].astype(jnp.float32)                # [BK, d]
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) + (sk - sq)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, *, interpret: bool):
    """Single head: q [Sq, d]; k, v [Sk, d] -> [Sq, d].
    Batched/bheaded use goes through ops.flash_attention (vmap)."""
    sq, d = q.shape
    sk = k.shape[0]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    scale = 1.0 / (d ** 0.5)
    grid = (sq // block_q, sk // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sq=sq, sk=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),   # running max m
            pltpu.VMEM((block_q,), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),  # accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out
