"""PQ asymmetric-distance computation (Pallas TPU): the DiskANN
baseline's in-memory guidance distances (``pq_adc``) and the compressed
data plane's batched ragged-pool scorer (``pq_adc_masked``).

TPU adaptation: the CPU implementation is M scalar L1-cache LUT gathers
per point; TPUs have no scalar gather units, so the lookup becomes a
one-hot matmul per subspace against the VMEM-resident LUT — MXU work
instead of pointer chasing (DESIGN.md §2). Codes stream in [BN, M] blocks;
the [M, 256] LUT stays resident.

``pq_adc_masked`` mirrors ``l2_topk_masked``: every query of a batch
carries its own LUT and its own ragged candidate pool (code rows padded
with id -1); one launch streams the pools in [BLOCK_Q, M, BC] blocks,
keeps a running per-query top-k in VMEM, and returns the ADC-nearest
candidates of every query — the selection stage of the PQ-compressed
probe wave.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.l2_topk import BLOCK_Q, _select_topk


def _kernel(lut_ref, codes_ref, out_ref, *, m: int):
    codes = codes_ref[...]                     # [BN, M] int32
    lut = lut_ref[...]                         # [M, 256] f32
    acc = jnp.zeros((codes.shape[0],), jnp.float32)
    for sub in range(m):                       # M static, unrolled
        onehot = (jax.lax.broadcasted_iota(
            jnp.int32, (codes.shape[0], 256), 1)
            == codes[:, sub][:, None]).astype(jnp.float32)
        # [BN, 256] @ [256] on the MXU
        acc = acc + jax.lax.dot_general(
            onehot, lut[sub], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_adc(lut: jax.Array, codes: jax.Array, block_n: int = 1024, *,
           interpret: bool) -> jax.Array:
    """lut [M, 256] f32; codes [N, M] int32/uint8 -> dists [N] f32."""
    m = lut.shape[0]
    n = codes.shape[0]
    codes = codes.astype(jnp.int32)
    block_n = min(block_n, n)
    pad = (-n) % block_n
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    grid = ((n + pad) // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, 256), lambda i: (0, 0)),       # LUT resident
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),   # codes stream
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n + pad,), jnp.float32),
        interpret=interpret,
    )(lut, codes)
    return out[:n]


def _masked_kernel(lut_ref, codes_ref, id_ref, out_d_ref, out_i_ref, *,
                   k: int, m: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, 3.4e38)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    ids = id_ref[...]                          # [BQ, BC] (-1 = padding)
    bq, bc = ids.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bc), 0)
    code_iota = jax.lax.broadcasted_iota(jnp.int32, (256, bc), 0)

    def query(r, acc):
        def subspace(sub, part):
            codes = codes_ref[r, pl.ds(sub, 1), :]            # [1, BC]
            onehot_t = (code_iota == codes).astype(jnp.float32)  # [256, BC]
            # every tile query's LUT row against query r's one-hot on
            # the MXU; HIGHEST keeps the looked-up values exact in f32
            return part + jax.lax.dot_general(
                lut_ref[sub], onehot_t, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)          # [BQ, BC]
        part = jax.lax.fori_loop(0, m, subspace,
                                 jnp.zeros((bq, bc), jnp.float32))
        return jnp.where(row == r, part, acc)

    acc = jax.lax.fori_loop(0, bq, query, jnp.zeros((bq, bc), jnp.float32))
    d2 = jnp.where(ids >= 0, acc, 3.4e38)      # mask ragged padding

    out_d_ref[...], out_i_ref[...] = _select_topk(
        out_d_ref[...], out_i_ref[...], d2, ids, k)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_c", "interpret"))
def pq_adc_masked(luts: jax.Array, codes: jax.Array, ids: jax.Array,
                  k: int = 10, block_c: int = 256, *, interpret: bool):
    """Ragged per-query PQ pools -> per-query ADC top-k.

    luts [Q, M, 256] f32 (one ADC table per query); codes [Q, C, M]
    uint8/int32; ids [Q, C] int32 candidate ids with -1 marking ragged
    padding. Returns (d2 [Q, k] ascending, ids [Q, k]); rows shorter
    than k pad with (3.4e38, -1). One launch scores the compressed
    pools of ALL queries of a batch (the PQ probe wave's hot loop),
    tiled BLOCK_Q queries x ``block_c`` candidates per grid step; the
    tile's LUTs ride as [M, BLOCK_Q, 256] and its codes as
    [BLOCK_Q, M, block_c] so each one-hot is a [256, block_c] slab."""
    qn, m = luts.shape[0], luts.shape[1]
    c = codes.shape[1]
    if c == 0:  # empty pools: all rows pad
        return (jnp.full((qn, k), 3.4e38, jnp.float32),
                jnp.full((qn, k), -1, jnp.int32))
    codes = codes.astype(jnp.int32)
    block_c = min(block_c, c)
    pad_c = (-c) % block_c
    pad_q = (-qn) % BLOCK_Q
    if pad_c or pad_q:
        luts = jnp.pad(luts, ((0, pad_q), (0, 0), (0, 0)))
        codes = jnp.pad(codes, ((0, pad_q), (0, pad_c), (0, 0)))
        ids = jnp.pad(ids, ((0, pad_q), (0, pad_c)), constant_values=-1)
    q_pad, c_pad = qn + pad_q, c + pad_c
    luts_t = jnp.transpose(luts, (1, 0, 2))      # [M, Q, 256]
    codes_t = jnp.transpose(codes, (0, 2, 1))    # [Q, M, C]

    grid = (q_pad // BLOCK_Q, c_pad // block_c)
    out_d, out_i = pl.pallas_call(
        functools.partial(_masked_kernel, k=k, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, BLOCK_Q, 256), lambda i, j: (0, i, 0)),
            pl.BlockSpec((BLOCK_Q, m, block_c), lambda i, j: (i, 0, j)),
            pl.BlockSpec((BLOCK_Q, block_c), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_Q, k), lambda i, j: (i, 0)),  # running top-k
            pl.BlockSpec((BLOCK_Q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(luts_t, codes_t, ids)
    out_d, out_i = out_d[:qn], out_i[:qn]
    valid = out_i >= 0
    out_d = jnp.where(valid, out_d, 3.4e38)
    return out_d, out_i
