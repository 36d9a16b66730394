"""Jit'd public wrappers for the Pallas kernels.

The raw kernels take ``interpret`` as a required keyword. These wrappers
resolve ``interpret=None`` from the default backend: compiled by Mosaic
on a TPU, the Pallas interpreter everywhere else (the CPU test tier).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import l2_topk as _l2
from repro.kernels import pq_adc as _pq


def default_interpret() -> bool:
    """What ``interpret=None`` resolves to on this process's backend."""
    return jax.default_backend() != "tpu"


def l2_topk(q, x, k: int = 10, block_n: int = 512,
            interpret: bool | None = None):
    """q [Q, d], x [N, d] -> (d2 [Q, k] ascending, ids [Q, k])."""
    interpret = default_interpret() if interpret is None else interpret
    return _l2.l2_topk(q, x, k=k, block_n=block_n, interpret=interpret)


def l2_topk_masked(q, pools, ids, k: int = 10, block_c: int = 256,
                   interpret: bool | None = None):
    """q [Q, d], pools [Q, C, d] (one dtype: float32, uint8 or int8),
    ids [Q, C] (-1 pads ragged rows) -> (d2 [Q, k] ascending, ids
    [Q, k]); short rows pad (3.4e38, -1). Integer distances are exact."""
    if q.dtype != pools.dtype:
        raise TypeError(f"{q.dtype} queries against {pools.dtype} pools")
    interpret = default_interpret() if interpret is None else interpret
    return _l2.l2_topk_masked(q, pools, ids, k=k, block_c=block_c,
                              interpret=interpret)


def pq_adc(lut, codes, block_n: int = 1024, interpret: bool | None = None):
    """lut [M, 256] f32, codes [N, M] -> dists [N] f32."""
    interpret = default_interpret() if interpret is None else interpret
    return _pq.pq_adc(lut, codes, block_n=block_n, interpret=interpret)


def pq_adc_masked(luts, codes, ids, k: int = 10, block_c: int = 256,
                  interpret: bool | None = None):
    """luts [Q, M, 256] f32, codes [Q, C, M], ids [Q, C] (-1 pads ragged
    rows) -> (d2 [Q, k] ascending, ids [Q, k]); short rows pad
    (3.4e38, -1)."""
    interpret = default_interpret() if interpret is None else interpret
    return _pq.pq_adc_masked(luts, codes, ids, k=k, block_c=block_c,
                             interpret=interpret)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q [B, H, Sq, d]; k, v [B, H, Sk, d] -> [B, H, Sq, d]."""
    interpret = default_interpret() if interpret is None else interpret
    fn = functools.partial(_fa.flash_attention, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return jax.vmap(jax.vmap(fn))(q, k, v)
