"""Algorithm 1: batched greedy (beam) search on a proximity graph.

Fixed-shape, fully jittable: the graph is a padded adjacency matrix
``nbrs [m_cap, R]`` (sentinel = m_cap for missing edges) over points
``A [m_cap, d]`` of which the first ``n_nodes`` rows are valid. Queries are
vmapped; the visited set is a [m_cap] bitmask per query (fine at the
aggregation-point scales PAG keeps in memory: m = p*n).

Also returns the expansion order (= the routing path the paper's
Routing-Path Redundancy and the asynchronous search consume) and the
per-hop best-unexpanded distances (consumed by the APP early-stop replay).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.distances import cdist2

INF = jnp.float32(3.4e38)


class SearchResult(NamedTuple):
    ids: jax.Array        # [Q, K] nearest candidate ids (padded m_cap)
    dists: jax.Array      # [Q, K] squared distances
    path: jax.Array       # [Q, H] expansion order (padded m_cap)
    path_dists: jax.Array  # [Q, H] distance of each expanded node
    n_hops: jax.Array     # [Q]


def _merge_beam(c_ids, c_d, c_exp, new_ids, new_d, L):
    """Merge the R new candidates into the beam and keep the best L.

    Invariant: the beam [L] is sorted ascending by distance, ties in the
    order a stable sort of (beam ++ new) by distance gives. So the merge
    is that stable sort, and each entry's place in it is a count:

    - beam entry i goes to ``i + #{j : nd_j < d_i}``;
    - new entry j goes to ``#{i : d_i <= nd_j}`` plus the new entries
      before it (``nd_j' < nd_j``, or equal with ``j' < j``).

    Dedup is needed only inside the new row: every scored node is marked
    visited, so a candidate already in the beam scores INF here, and a
    repeat of an earlier id in the row gets INF. Slot p of the new beam
    holds new entry j where its place is p, else beam entry p - k_p, where
    k_p counts the new entries placed before p: one of R + 1 static
    shifts of the beam. No sort is needed."""
    R = new_ids.shape[0]
    j = jnp.arange(R)
    earlier = j[None, :] < j[:, None]                      # [R, R]: j' < j
    repeat = jnp.any((new_ids[None, :] == new_ids[:, None]) & earlier, 1)
    nd = jnp.where(repeat, INF, new_d)
    ahead = (nd[None, :] < nd[:, None]) | ((nd[None, :] == nd[:, None])
                                           & earlier)
    pos_n = (jnp.sum(c_d[None, :] <= nd[:, None], 1)
             + jnp.sum(ahead, 1))                          # [R]
    p = jnp.arange(L)
    hit = pos_n[None, :] == p[:, None]                     # [L, R]
    is_new = jnp.any(hit, 1)
    k = jnp.sum(pos_n[None, :] < p[:, None], 1)            # [L]

    def place(beam, new, fill):
        out = beam
        for s in range(1, min(R, L - 1) + 1):   # k_p <= p < L
            shifted = jnp.concatenate([jnp.full((s,), fill, beam.dtype),
                                       beam[:L - s]])
            out = jnp.where(k == s, shifted, out)
        return jnp.where(is_new, jnp.max(jnp.where(hit, new[None, :], fill),
                                         1), out)

    return (place(c_ids, new_ids, jnp.iinfo(jnp.int32).min),
            place(c_d, nd, -jnp.inf),
            place(c_exp, jnp.zeros((R,), bool), False))


@functools.partial(jax.jit, static_argnames=("L", "K", "max_hops"))
def greedy_search(A, nbrs, n_nodes, entry, queries, *, L: int = 64,
                  K: int = 10, max_hops: int = 0) -> SearchResult:
    """Beam search. A [m_cap, d]; nbrs [m_cap, R]; entry scalar id or
    per-query [Q] ids; queries [Q, d]. Stops when the beam has no
    unexpanded candidates."""
    m_cap = A.shape[0]
    max_hops = max_hops or (L + 32)
    entries = jnp.broadcast_to(jnp.asarray(entry, jnp.int32),
                               (queries.shape[0],))

    def one(q, entry):
        d_entry = cdist2(q[None], A[entry][None])[0, 0]
        c_ids = jnp.full((L,), m_cap, jnp.int32).at[0].set(entry)
        c_d = jnp.full((L,), INF).at[0].set(d_entry)
        c_exp = jnp.zeros((L,), bool)
        visited = jnp.zeros((m_cap + 1,), bool).at[entry].set(True)
        path = jnp.full((max_hops,), m_cap, jnp.int32)
        path_d = jnp.full((max_hops,), INF)

        def cond(state):
            c_ids, c_d, c_exp, visited, path, path_d, hop = state
            frontier = (~c_exp) & (c_d < INF)
            return (hop < max_hops) & jnp.any(frontier)

        def body(state):
            c_ids, c_d, c_exp, visited, path, path_d, hop = state
            masked = jnp.where(c_exp, INF, c_d)
            j = jnp.argmin(masked)
            cur = c_ids[j]
            cur_d = c_d[j]
            c_exp = c_exp.at[j].set(True)
            path = path.at[hop].set(cur)
            path_d = path_d.at[hop].set(cur_d)

            nb = nbrs[jnp.minimum(cur, m_cap - 1)]          # [R]
            nb = jnp.where(cur >= m_cap, m_cap, nb)
            valid = (nb < n_nodes) & ~visited[jnp.minimum(nb, m_cap)]
            nb_safe = jnp.minimum(nb, m_cap - 1)
            nd = cdist2(q[None], A[nb_safe])[0]
            nd = jnp.where(valid, nd, INF)
            visited = visited.at[jnp.minimum(nb, m_cap)].set(True)

            c_ids, c_d, c_exp = _merge_beam(c_ids, c_d, c_exp,
                                            nb.astype(jnp.int32), nd, L)
            return c_ids, c_d, c_exp, visited, path, path_d, hop + 1

        state = (c_ids, c_d, c_exp, visited, path, path_d,
                 jnp.zeros((), jnp.int32))
        c_ids, c_d, c_exp, visited, path, path_d, hops = \
            jax.lax.while_loop(cond, body, state)

        # the beam is sorted: its first K are the K nearest
        return SearchResult(c_ids[:K], c_d[:K], path, path_d, hops)

    return jax.vmap(one)(queries, entries)


@functools.partial(jax.jit, static_argnames=("R",))
def robust_prune(cand_ids, cand_d, A, n_nodes, alpha, *, R: int):
    """DiskANN/RNG-style diverse pruning (vmapped over rows).

    cand_ids/cand_d [B, C] sorted-or-not candidate sets; returns [B, R]
    padded with m_cap. Occlusion rule: drop y if exists selected s with
    alpha * δ(s, y) < δ(p, y)  (squared-distance form of Def 5 / DiskANN).
    """
    m_cap = A.shape[0]

    def one(ids, ds):
        order = jnp.argsort(ds)
        ids, ds = ids[order], ds[order]
        alive = (ids < n_nodes) & (ds < INF)
        # dedup
        so = jnp.argsort(ids)
        sid = ids[so]
        dup = jnp.concatenate([jnp.zeros(1, bool), sid[1:] == sid[:-1]])
        alive = alive.at[so].set(alive[so] & ~dup)
        out = jnp.full((R,), m_cap, jnp.int32)

        def body(i, carry):
            alive, out = carry
            masked = jnp.where(alive, ds, INF)
            j = jnp.argmin(masked)
            ok = masked[j] < INF
            sel = ids[j]
            out = out.at[i].set(jnp.where(ok, sel, m_cap))
            alive = alive.at[j].set(False)
            # occlude: y dropped if alpha^2-scaled δ(sel, y) < δ(p, y)
            sel_v = A[jnp.minimum(sel, m_cap - 1)]
            d_sel = cdist2(sel_v[None], A[jnp.minimum(ids, m_cap - 1)])[0]
            occl = (alpha * d_sel < ds) & ok
            alive = alive & ~occl
            return alive, out

        alive, out = jax.lax.fori_loop(0, R, body, (alive, out))
        return out

    return jax.vmap(one)(cand_ids, cand_d)
