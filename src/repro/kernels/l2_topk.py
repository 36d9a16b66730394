"""Fused squared-L2 distance + running top-k partition scan (Pallas TPU).

The single hot loop of DSANN: partition full-scans (Alg 5 line "full
scan"), DRS residual assignment (Alg 3 line 16) and the SPANN baseline all
reduce to "stream blocks of points past a resident query tile, keep the
k nearest". The kernel keeps the query tile and the running (dist, id)
top-k in VMEM across grid steps, computes -2*q.x^T on the MXU, and merges
each block with an unrolled selection pass — distances never round-trip
to HBM (the jnp path materializes the full [Q, N] matrix).

Everything inside the kernels is 2-D and gather-free so that Mosaic (the
TPU kernel compiler) accepts it: selection is a masked min per step, not
an indexed read, and per-vector norms travel as [Q, 1] / [1, N] blocks.

The masked kernel also takes 1-byte integer pools and queries (uint8,
int8: BIGANN-style bases), which reach it in their own type and are
widened to float32 inside the kernel (``_widen``); every difference,
square and partial sum is then an integer below 2**24 up to d = 129
(uint8), so the distances are exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 8          # query rows per tile of the masked kernels (sublanes)
_NO_COL = 2 ** 30    # column sentinel of the selection's masked min


def _select_topk(run_d, run_i, blk_d, blk_i, k: int):
    """Gather-free k-selection over (running top-k ++ block) columns.

    run_d/run_i [R, k] hold the running top-k, blk_d/blk_i [R, BC] the
    new block. Each of the k unrolled steps takes the row minimum, finds
    its first column (running entries before block entries, like an
    argmin over the concatenation) with a masked int min, reads the id
    through a one-hot masked max, and retires that column. Returns the
    new running (d [R, k] ascending, ids [R, k])."""
    col_r = jax.lax.broadcasted_iota(jnp.int32, run_d.shape, 1)
    col_b = jax.lax.broadcasted_iota(jnp.int32, blk_d.shape, 1)
    out_d = jnp.full(run_d.shape, 3.4e38, jnp.float32)
    out_i = jnp.full(run_i.shape, -1, jnp.int32)
    for t in range(k):
        best = jnp.minimum(jnp.min(run_d, axis=1, keepdims=True),
                           jnp.min(blk_d, axis=1, keepdims=True))
        j_r = jnp.min(jnp.where(run_d == best, col_r, _NO_COL), axis=1,
                      keepdims=True)
        j_b = jnp.min(jnp.where(blk_d == best, col_b, _NO_COL), axis=1,
                      keepdims=True)
        hit_r = col_r == j_r
        hit_b = (col_b == j_b) & (j_r == _NO_COL)
        best_i = jnp.maximum(
            jnp.max(jnp.where(hit_r, run_i, -1), axis=1, keepdims=True),
            jnp.max(jnp.where(hit_b, blk_i, -1), axis=1, keepdims=True))
        out_d = jnp.where(col_r == t, best, out_d)
        out_i = jnp.where(col_r == t, best_i, out_i)
        run_d = jnp.where(hit_r, 3.4e38, run_d)
        blk_d = jnp.where(hit_b, 3.4e38, blk_d)
    return out_d, out_i


def _kernel(q_ref, x_ref, qn_ref, xn_ref, out_d_ref, out_i_ref, *,
            k: int, block_n: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, 3.4e38)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    q = q_ref[...].astype(jnp.float32)            # [Q, d] resident
    x = x_ref[...].astype(jnp.float32)            # [BN, d] streamed block
    # d2 = |q|^2 - 2 q.x + |x|^2 ; the matmul hits the MXU
    d2 = qn_ref[...] - 2.0 * jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + xn_ref[...]
    d2 = jnp.maximum(d2, 0.0)                     # [Q, BN]
    ids = (i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, d2.shape, 1))

    out_d_ref[...], out_i_ref[...] = _select_topk(
        out_d_ref[...], out_i_ref[...], d2, ids, k)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "interpret"))
def l2_topk(q: jax.Array, x: jax.Array, k: int = 10,
            block_n: int = 512, *, interpret: bool):
    """q [Q, d], x [N, d] -> (d2 [Q, k] ascending, ids [Q, k])."""
    qn, d = q.shape
    n = x.shape[0]
    block_n = min(block_n, n)
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=3.4e18)
    n_pad = n + pad
    q_norm = jnp.sum(q.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    x_norm = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)[None, :]

    grid = (n_pad // block_n,)
    out_d, out_i = pl.pallas_call(
        functools.partial(_kernel, k=k, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((qn, d), lambda i: (0, 0)),        # q resident
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # x streamed
            pl.BlockSpec((qn, 1), lambda i: (0, 0)),        # |q|^2
            pl.BlockSpec((1, block_n), lambda i: (0, i)),   # |x|^2
        ],
        out_specs=[
            pl.BlockSpec((qn, k), lambda i: (0, 0)),        # running top-k
            pl.BlockSpec((qn, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, x, q_norm, x_norm)
    # drop padded rows (their distance is astronomically large)
    valid = out_i < n
    out_d = jnp.where(valid, out_d, 3.4e38)
    out_i = jnp.where(valid, out_i, -1)
    return out_d, out_i


def _widen(x):
    """A block in float32. Mosaic casts no unsigned type to a float
    directly, so unsigned integers go through int32 (exact)."""
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _masked_kernel(q_ref, x_ref, id_ref, out_d_ref, out_i_ref, *, k: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, 3.4e38)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    q = _widen(q_ref[...])                        # [BQ, d] query tile
    ids = id_ref[...]                             # [BQ, BC] (-1 = padding)
    bq, bc = ids.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bc), 0)
    ones = jnp.ones(q.shape, jnp.float32)
    d2 = jnp.zeros((bq, bc), jnp.float32)
    for r in range(bq):
        # query r's pool block against query r: the ones-matmul sums the
        # squared differences over d AND lays the result along lanes
        diff = _widen(x_ref[r]) - q[r:r + 1, :]               # [BC, d]
        d2_r = jax.lax.dot_general(
            ones, diff * diff, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)               # [BQ, BC]
        d2 = jnp.where(row == r, d2_r, d2)
    d2 = jnp.where(ids >= 0, d2, 3.4e38)          # mask ragged padding

    out_d_ref[...], out_i_ref[...] = _select_topk(
        out_d_ref[...], out_i_ref[...], d2, ids, k)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_c", "interpret"))
def l2_topk_masked(q: jax.Array, pools: jax.Array, ids: jax.Array,
                   k: int = 10, block_c: int = 256, *, interpret: bool):
    """Ragged per-query candidate pools -> per-query top-k.

    q [Q, d]; pools [Q, C, d] (row c of query i = candidate vector),
    both float32, uint8 or int8; ids [Q, C] int32 candidate ids with -1
    marking ragged padding.
    Returns (d2 [Q, k] ascending, ids [Q, k]); rows shorter than k are
    padded with (3.4e38, -1). One kernel launch scans the pools of ALL
    queries of a batch (the batched-search hot loop), tiled BLOCK_Q
    queries x ``block_c`` candidates per grid step."""
    qn, d = q.shape
    c = pools.shape[1]
    block_c = min(block_c, max(c, 1))
    pad_c = (-c) % block_c
    pad_q = (-qn) % BLOCK_Q
    if pad_c or pad_q:
        q = jnp.pad(q, ((0, pad_q), (0, 0)))
        pools = jnp.pad(pools, ((0, pad_q), (0, pad_c), (0, 0)))
        ids = jnp.pad(ids, ((0, pad_q), (0, pad_c)), constant_values=-1)
    q_pad, c_pad = qn + pad_q, c + pad_c

    grid = (q_pad // BLOCK_Q, c_pad // block_c)
    out_d, out_i = pl.pallas_call(
        functools.partial(_masked_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, d), lambda i, j: (i, 0)),  # query tile
            pl.BlockSpec((BLOCK_Q, block_c, d), lambda i, j: (i, j, 0)),
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
    )(q, pools, ids)
    out_d, out_i = out_d[:qn], out_i[:qn]
    valid = out_i >= 0
    out_d = jnp.where(valid, out_d, 3.4e38)
    return out_d, out_i
