"""Proximity-graph construction (Vamana-style batched insert rounds).

Offline build = Python/numpy orchestration over jitted batch kernels
(greedy_search + robust_prune), the same structure DiskANN uses
(CPU-orchestrated). Two passes with alpha 1.0 -> 1.2, reverse-edge
insertion with overflow pruning.

The graph lives in a fixed-capacity arena (m_cap rows) so later PAG
promotion (Alg 3 step 3) can insert new nodes without reallocation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distances import cdist2, topk_l2
from repro.core.graph_search import greedy_search, robust_prune


NATIVE_DTYPES = ("uint8", "int8")   # integer bases kept in their own type


def vector_dtype(dtype) -> str:
    """Element type the index keeps a base of ``dtype`` in, on the device
    and in storage: 1-byte integer bases (BIGANN's uint8) as they are,
    every other base as float32."""
    name = np.dtype(dtype).name
    return name if name in NATIVE_DTYPES else "float32"


@dataclasses.dataclass
class PG:
    """Mutable proximity-graph arena.

    nbrs columns [0, R_prune) are alpha-RNG-pruned edges (rewritten by
    insert/reverse passes); columns [R_prune, R_total) are NSW-style random
    long-range edges fixed at init — they guarantee navigability across
    strongly clustered data (greedy beam search otherwise stalls at
    cluster boundaries; see tests/test_pag.py).

    ``A`` is float32 on the host, where the build computes (exact on
    1-byte integers); ``dtype`` is the base's element type
    (``vector_dtype``), in which ``device_arrays`` uploads it."""
    A: np.ndarray          # [m_cap, d] float32 (rows >= n_nodes are zeros)
    nbrs: np.ndarray       # [m_cap, R_total] int32, sentinel = m_cap
    n_nodes: int
    entry: int
    R_prune: int = 0       # 0 -> whole width prunable
    dtype: str = "float32"  # element type of the aggregation points

    def __post_init__(self):
        if self.R_prune == 0:
            self.R_prune = self.nbrs.shape[1]

    @property
    def m_cap(self) -> int:
        return self.A.shape[0]

    @property
    def R(self) -> int:
        return self.R_prune

    def native_A(self) -> np.ndarray:
        """The aggregation points in the base's element type."""
        return self.A if self.A.dtype == self.dtype else self.A.astype(
            self.dtype)

    def device_arrays(self):
        return (jnp.asarray(self.native_A()), jnp.asarray(self.nbrs),
                jnp.int32(self.n_nodes), jnp.int32(self.entry))


def _medoid(x: np.ndarray) -> int:
    mean = x.mean(axis=0, keepdims=True)
    return int(np.asarray(cdist2(jnp.asarray(mean), jnp.asarray(x))).argmin())


MAX_REV_ADD = 8  # reverse-edge additions kept per destination per batch


def _bucket(n: int) -> int:
    """Row count a device call is padded to: the next power of two (at
    least 64), so calls with varying row counts reuse a few compiled
    programs. Every vmapped row is independent, so pad rows change no
    real row's result."""
    return 1 << max(6, (n - 1).bit_length())


def _reverse_edges(pg: PG, ids: np.ndarray, alpha2: float):
    """Insert reverse edges id -> (its new nbrs); prune overflowing rows.

    Vectorized: group by destination (sort + unique), cap additions per
    destination at MAX_REV_ADD, compact valid-existing + additions into a
    padded matrix, and robust-prune only the rows that overflow R.
    """
    m_cap, R = pg.m_cap, pg.R_prune
    src = np.repeat(ids.astype(np.int32), R)
    dst = pg.nbrs[ids, :R].reshape(-1)
    ok = dst < pg.n_nodes
    src, dst = src[ok], dst[ok]
    if len(dst) == 0:
        return
    order = np.argsort(dst, kind="stable")
    dst_s, src_s = dst[order], src[order]
    uniq, starts, counts = np.unique(dst_s, return_index=True,
                                     return_counts=True)
    n_u = len(uniq)
    adds = np.full((n_u, MAX_REV_ADD), m_cap, np.int32)
    take = np.minimum(counts, MAX_REV_ADD)
    for j in range(MAX_REV_ADD):  # MAX_REV_ADD is tiny; rows vectorized
        sel = take > j
        adds[sel, j] = src_s[starts[sel] + j]

    W = R + MAX_REV_ADD
    mat = np.concatenate([pg.nbrs[uniq, :R], adds], axis=1)  # [n_u, W]
    valid = mat < pg.n_nodes
    # dedup within row (keep first occurrence)
    sort_idx = np.argsort(np.where(valid, mat, m_cap + 1), axis=1,
                          kind="stable")
    mat_s = np.take_along_axis(mat, sort_idx, axis=1)
    dup = np.zeros_like(valid)
    dup[:, 1:] = mat_s[:, 1:] == mat_s[:, :-1]
    valid_s = (mat_s < pg.n_nodes) & ~dup
    n_valid = valid_s.sum(axis=1)
    # compact: stable-sort validity so real entries come first
    comp_idx = np.argsort(~valid_s, axis=1, kind="stable")
    compact = np.take_along_axis(mat_s, comp_idx, axis=1)
    compact = np.where(
        np.arange(W)[None, :] < n_valid[:, None], compact, m_cap)

    fits = n_valid <= R
    pg.nbrs[uniq[fits], :R] = compact[fits, :R]

    over = ~fits
    if over.any():
        rows = uniq[over]
        cand = compact[over]                                  # [B, W]
        cand_safe = np.minimum(cand, m_cap - 1)
        diffs = pg.A[cand_safe] - pg.A[rows][:, None, :]
        cd = np.einsum("bcd,bcd->bc", diffs, diffs).astype(np.float32)
        cd = np.where(cand < pg.n_nodes, cd, np.float32(3.4e38))
        # the overflow count differs every call: pad it (``_bucket``)
        # with rows that have no live candidate, and drop them after
        pad = _bucket(len(rows)) - len(rows)
        cand = np.pad(cand, ((0, pad), (0, 0)), constant_values=m_cap)
        cd = np.pad(cd, ((0, pad), (0, 0)), constant_values=3.4e38)
        pruned = np.asarray(robust_prune(
            jnp.asarray(cand), jnp.asarray(cd), jnp.asarray(pg.A),
            jnp.int32(pg.n_nodes), jnp.float32(alpha2), R=R))
        pg.nbrs[rows, :R] = pruned[:len(rows)]


def build_pg(x: np.ndarray, R: int = 16, L: int = 48,
             alpha: float = 1.2, m_cap: Optional[int] = None,
             batch: int = 1024, seed: int = 0, n_random: int = 2,
             passes: Tuple[float, ...] = (1.0, None)) -> PG:
    """Build a Vamana-style PG over x [m, d] (+n_random NSW long edges)."""
    m, d = x.shape
    m_cap = m_cap or m
    assert m_cap >= m
    rng = np.random.default_rng(seed)

    A = np.zeros((m_cap, d), np.float32)
    A[:m] = x
    nbrs = np.full((m_cap, R + n_random), m_cap, np.int32)
    # random init graph (prunable region) + fixed random long edges
    nbrs[:m, :] = rng.integers(0, m, size=(m, R + n_random))
    pg = PG(A=A, nbrs=nbrs, n_nodes=m, entry=_medoid(x), R_prune=R)

    passes = tuple(a if a is not None else alpha for a in passes)
    for a in passes:
        alpha2 = float(a * a)
        order = rng.permutation(m)
        for i in range(0, m, batch):
            ids = order[i:i + batch]
            if len(ids) < batch:  # fixed shapes: pad by repeating (benign)
                ids = np.concatenate([ids, order[: batch - len(ids)]])
            _insert_batch(pg, ids, L, alpha2)
    repair_connectivity(pg)
    return pg


def reachable_mask(pg: PG) -> np.ndarray:
    seen = np.zeros(pg.n_nodes, bool)
    seen[pg.entry] = True
    frontier = np.array([pg.entry])
    while len(frontier):
        nxt = pg.nbrs[frontier].reshape(-1)
        nxt = nxt[nxt < pg.n_nodes]
        nxt = nxt[~seen[nxt]]
        if len(nxt) == 0:
            break
        nxt = np.unique(nxt)
        seen[nxt] = True
        frontier = nxt
    return seen


def repair_connectivity(pg: PG, sample: int = 256):
    """Link unreachable nodes to their nearest reachable node (both
    directions), guaranteeing the entry point reaches every node. RNG-
    family graphs are connected in theory; batched approximate builds can
    drop bridge edges on strongly clustered data — this restores them,
    mirroring DiskANN implementations' final connect pass."""
    m_cap = pg.m_cap
    for _ in range(100):
        seen = reachable_mask(pg)
        if seen.all():
            return
        missing = np.where(~seen)[0]
        inside = np.where(seen)[0]
        sub = missing[:: max(len(missing) // sample, 1)][:sample]
        d2 = np.asarray(cdist2(jnp.asarray(pg.A[sub]),
                               jnp.asarray(pg.A[inside])))
        nearest = inside[np.argmin(d2, axis=1)]
        for a, b in zip(sub.tolist(), nearest.tolist()):
            for u, v in ((a, b), (b, a)):
                row = pg.nbrs[u]
                free = np.where(row >= m_cap)[0]
                row[free[0] if len(free) else -1] = v


def _insert_batch(pg: PG, ids: np.ndarray, L: int, alpha2: float):
    A_dev, nbrs_dev, n_nodes, entry = pg.device_arrays()
    b = len(ids)
    pad = _bucket(b) - b
    q = pg.A[ids]
    q = np.concatenate([q, np.repeat(q[:1], pad, axis=0)])
    res = greedy_search(A_dev, nbrs_dev, n_nodes, entry, jnp.asarray(q),
                        L=L, K=L)
    # candidates: beam results + current neighbors + visited path
    cand = np.concatenate([np.asarray(res.ids)[:b],
                           np.asarray(res.path)[:b], pg.nbrs[ids]], axis=1)
    m_cap = pg.m_cap
    cand_safe = np.minimum(cand, m_cap - 1)
    diffs = pg.A[cand_safe] - pg.A[ids][:, None, :]
    cd = np.einsum("bcd,bcd->bc", diffs, diffs).astype(np.float32)
    invalid = (cand >= pg.n_nodes) | (cand == ids[:, None])
    cd = np.where(invalid, np.float32(3.4e38), cd)
    pruned = np.asarray(robust_prune(
        jnp.asarray(np.pad(cand.astype(np.int32), ((0, pad), (0, 0)),
                           constant_values=m_cap)),
        jnp.asarray(np.pad(cd, ((0, pad), (0, 0)), constant_values=3.4e38)),
        A_dev, jnp.int32(pg.n_nodes), jnp.float32(alpha2), R=pg.R_prune))
    pg.nbrs[ids, :pg.R_prune] = pruned[:b]
    _reverse_edges(pg, ids, alpha2)


def insert_nodes(pg: PG, new_x: np.ndarray, L: int = 48,
                 alpha: float = 1.2, batch: int = 2048) -> np.ndarray:
    """Insert new points into the arena (PAG promotion). Returns their ids.

    Links them ``batch`` at a time: one traversal's visited masks take
    ``batch x m_cap`` bytes of device memory."""
    k = new_x.shape[0]
    assert pg.n_nodes + k <= pg.m_cap, "PG arena capacity exceeded"
    ids = np.arange(pg.n_nodes, pg.n_nodes + k, dtype=np.int32)
    pg.A[ids] = new_x
    pg.n_nodes += k
    n_rand = pg.nbrs.shape[1] - pg.R_prune
    if n_rand:
        rng = np.random.default_rng(int(pg.n_nodes))
        pg.nbrs[ids, pg.R_prune:] = rng.integers(
            0, pg.n_nodes, size=(k, n_rand))
    for s in range(0, k, batch):
        _insert_batch(pg, ids[s:s + batch], L, float(alpha * alpha))
    return ids


def exact_pg(x: np.ndarray, R: int = 16) -> PG:
    """Exact KNN graph (tiny oracle for tests)."""
    m = x.shape[0]
    ids, _ = topk_l2(jnp.asarray(x), jnp.asarray(x), R + 1)
    ids = np.asarray(ids)
    nbrs = np.zeros((m, R), np.int32)
    for i in range(m):
        row = [j for j in ids[i] if j != i][:R]
        nbrs[i, :len(row)] = row
        nbrs[i, len(row):] = m
    return PG(A=x.astype(np.float32).copy(), nbrs=nbrs, n_nodes=m,
              entry=_medoid(x))
