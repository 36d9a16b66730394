"""Plain references that decide ``correct``, and the lower-precision
control that has to fail them.

Nothing here imports the program or takes anything it made: the
references read the base and queries the benchmark generated, and the
kernel launches' own inputs as the program handed them to the kernel.

- ``exact_knn``: exact top-k by squared L2 over the whole base, on the
  device at ``highest`` matmul precision, in chunks of queries.
- ``answer_d2``: the squared distance of each returned (query, id), in
  float64 on the host.
- ``recall``: the share of the exact k nearest among the returned k.
- ``pool_d2_l2`` / ``pool_d2_pq``: every pooled candidate's distance of
  a captured ``l2_topk_masked`` / ``pq_adc_masked`` launch, in float32
  (``highest``) or, for the control, bfloat16.
- ``kernel_gap``: the widest relative gap between a launch's returned
  top-k and the float32 reference of its pool.
- ``control_l2_topk_masked`` / ``control_pq_adc_masked``: the reference
  computed in bfloat16 (the precision below the float32 that DEEP-1B
  states), with the kernels' signatures, to put in their place.

Integer vectors (``uint8``, ``int8``) go through float32 unchanged:
every coordinate, product, partial sum and squared distance is an
integer below 2**24, so float32 holds each exactly and ``exact_knn``
and ``pool_d2_l2`` give the exact integer distances.
``check_exact`` refuses a width at which that would no longer hold
(d = 129 is the widest for ``uint8``, so BIGANN's 128 fits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INF = np.float32(3.4e38)
HIGHEST = jax.lax.Precision.HIGHEST
F32_EXACT = 2 ** 24     # every integer up to this is exact in float32


def int_bound(dtype, d: int) -> int:
    """The largest magnitude that any term, partial sum or result of the
    squared-L2 references takes on integer vectors of ``dtype`` and
    width ``d``: ``|q|^2 - 2 q.x`` reaches 2 d m^2 (unsigned) or
    3 d m^2 (signed), m the type's largest magnitude; a squared
    distance reaches d (max - min)^2."""
    info = np.iinfo(dtype)
    m = max(-int(info.min), int(info.max))
    lead = 3 if info.min < 0 else 2
    return max(lead * d * m * m, d * (int(info.max) - int(info.min)) ** 2)


def check_exact(dtype, d: int) -> None:
    """Raise where float32 would not hold integer distances exactly."""
    if np.issubdtype(dtype, np.integer) and int_bound(dtype, d) >= F32_EXACT:
        raise ValueError(f"{np.dtype(dtype).name} vectors of width {d}: "
                         f"squared distances reach {int_bound(dtype, d)}, "
                         f"beyond float32's exact integers (2**24)")


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_chunk(q, base, base_sq, *, k: int):
    qx = jnp.matmul(q, base.T, precision=HIGHEST)
    d2 = jnp.sum(q * q, axis=1)[:, None] - 2.0 * qx + base_sq[None, :]
    _, ids = jax.lax.top_k(-d2, k)
    return ids


def exact_knn(base: np.ndarray, queries: np.ndarray, k: int,
              chunk: int = 128) -> np.ndarray:
    """Exact k nearest base ids [Q, k] of every query, nearest first
    (among equal distances, in no particular order)."""
    check_exact(base.dtype, base.shape[1])
    base_d = jnp.asarray(base, jnp.float32)
    base_sq = jnp.sum(base_d * base_d, axis=1)
    out = []
    for s in range(0, len(queries), chunk):
        q = np.asarray(queries[s:s + chunk], np.float32)
        n = len(q)
        if n < chunk:  # one compiled shape for every chunk
            q = np.concatenate([q, np.repeat(q[:1], chunk - n, axis=0)])
        out.append(np.asarray(_knn_chunk(jnp.asarray(q), base_d, base_sq,
                                         k=k))[:n])
    return np.concatenate(out).astype(np.int64)


def answer_d2(base: np.ndarray, queries: np.ndarray, q_idx: np.ndarray,
              ids: np.ndarray) -> np.ndarray:
    """float64 squared distance of base[ids[i, j]] to queries[q_idx[i]];
    ids outside the base read as row 0 (callers mask them)."""
    safe = np.where((ids >= 0) & (ids < len(base)), ids, 0)
    diff = base[safe].astype(np.float64) \
        - queries[q_idx].astype(np.float64)[:, None, :]
    return np.einsum("qkd,qkd->qk", diff, diff)


def recall(base: np.ndarray, queries: np.ndarray, q_idx: np.ndarray,
           result_ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    """Share of the exact k nearest found among the returned k, over the
    answers to ``queries[q_idx]``; ``gt_ids`` [N, k] are their exact k
    nearest (``exact_knn``).

    A float base counts the returned ids that are among ``gt_ids``. On an
    integer base distances are exact and tie often, so the k-th nearest
    may be any of several ids: there a returned id of the base counts
    where its exact distance is no larger than the exact k-th nearest
    distance, each id once and at most k a query (ann-benchmarks' k-NN
    recall, with no epsilon)."""
    if not np.issubdtype(base.dtype, np.integer):
        hits = sum(len(set(r[:k].tolist()) & set(g[:k].tolist()))
                   for r, g in zip(result_ids, gt_ids))
        return hits / (len(gt_ids) * k)
    ids = result_ids[:, :k]
    kth = answer_d2(base, queries, q_idx, gt_ids[:, k - 1:k])
    near = (ids >= 0) & (ids < len(base)) \
        & (answer_d2(base, queries, q_idx, ids) <= kth)
    kept = np.sort(np.where(near, ids, -1), axis=1)
    distinct = (kept >= 0) & np.concatenate(
        [np.ones((len(kept), 1), bool), kept[:, 1:] != kept[:, :-1]], axis=1)
    hits = np.minimum(distinct.sum(axis=1), k).sum()
    return float(hits) / (len(gt_ids) * k)


# ------------------------------------------------------ kernel references
@functools.partial(jax.jit, static_argnames=("dtype",))
def pool_d2_l2(q, pools, *, dtype=jnp.float32):
    """q [Q, d], pools [Q, C, d] -> squared distances [Q, C] float32,
    the differences and their squares held in ``dtype``, summed in
    float32. Exact on integer vectors in float32 (module docstring)."""
    diff = pools.astype(dtype) - q.astype(dtype)[:, None, :]
    sq = diff * diff
    info = jnp.finfo(dtype)
    if info.bits < 32:
        # XLA may keep the product in float32 (excess precision); round
        # it as a kernel holding it in ``dtype`` would. On integer
        # vectors the differences are exact in bfloat16, their squares
        # are not.
        sq = jax.lax.reduce_precision(sq, exponent_bits=info.nexp,
                                      mantissa_bits=info.nmant)
    return jnp.sum(sq, axis=-1, dtype=jnp.float32)


@functools.partial(jax.jit, static_argnames=("dtype",))
def pool_d2_pq(luts, codes, *, dtype=jnp.float32):
    """luts [Q, M, 256], codes [Q, C, M] -> ADC distances [Q, C] float32,
    the looked-up entries held in ``dtype``."""
    idx = jnp.transpose(codes.astype(jnp.int32), (0, 2, 1))   # [Q, M, C]
    vals = jnp.take_along_axis(luts.astype(dtype), idx, axis=2)
    return jnp.sum(vals, axis=1, dtype=jnp.float32)


def _topk_masked(d2, ids, k: int):
    d2 = jnp.where(ids >= 0, d2, INF)
    c = d2.shape[1]
    if c < k:
        d2 = jnp.pad(d2, ((0, 0), (0, k - c)), constant_values=INF)
        ids = jnp.pad(ids, ((0, 0), (0, k - c)), constant_values=-1)
    neg, pos = jax.lax.top_k(-d2, k)
    out_i = jnp.take_along_axis(ids, pos, axis=1)
    out_d = jnp.where(out_i >= 0, -neg, INF)
    return out_d, jnp.where(out_i >= 0, out_i, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "block_c", "interpret"))
def control_l2_topk_masked(q, pools, ids, k: int = 10, block_c: int = 256,
                           interpret=None):
    """The l2 reference in bfloat16, with ``l2_topk_masked``'s call."""
    return _topk_masked(pool_d2_l2(q, pools, dtype=jnp.bfloat16), ids, k)


@functools.partial(jax.jit, static_argnames=("k", "block_c", "interpret"))
def control_pq_adc_masked(luts, codes, ids, k: int = 10, block_c: int = 256,
                          interpret=None):
    """The ADC reference in bfloat16, with ``pq_adc_masked``'s call."""
    return _topk_masked(pool_d2_pq(luts, codes, dtype=jnp.bfloat16), ids, k)


def kernel_gap(ref_d2: np.ndarray, ids: np.ndarray, out_d: np.ndarray,
               out_i: np.ndarray) -> float:
    """Widest relative gap of one launch against its float32 reference.

    ref_d2, ids [Q, C]: the reference distance of every pooled candidate
    and the candidate ids the launch was given (-1 pads). out_d, out_i
    [Q, k]: what the launch returned. Two gaps per returned entry: its
    distance against the reference distance of the id it names, and the
    reference distance of the i-th returned id (in the reference's order)
    against the reference's i-th nearest. A returned id that is not in
    the pool, or a missing entry where the pool had one, is an infinite
    gap. Ties at equal distance cost nothing."""
    ref_d2 = np.where(ids >= 0, ref_d2, np.inf).astype(np.float64)
    k = out_i.shape[1]
    want = np.sort(ref_d2, axis=1)[:, :k]
    if want.shape[1] < k:
        want = np.pad(want, ((0, 0), (0, k - want.shape[1])),
                      constant_values=np.inf)
    worst = 0.0
    for r in range(ids.shape[0]):
        order = np.argsort(ids[r], kind="stable")
        sorted_ids = ids[r][order]
        got = np.full(k, np.inf)
        for j, (d, i) in enumerate(zip(out_d[r], out_i[r])):
            if i < 0:
                continue
            pos = np.searchsorted(sorted_ids, i)
            if pos >= len(sorted_ids) or sorted_ids[pos] != i:
                return float("inf")
            ref = ref_d2[r, order[pos]]
            got[j] = ref
            worst = max(worst, abs(float(d) - ref) / max(ref, 1e-30))
        got = np.sort(got)
        finite = np.isfinite(want[r])
        if not np.all(np.isfinite(got[finite])):
            return float("inf")
        rel = (got[finite] - want[r][finite]) / np.maximum(
            want[r][finite], 1e-30)
        worst = max(worst, float(rel.max(initial=0.0)))
    return worst
