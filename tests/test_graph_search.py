"""The graph phase's counting merge against the argsort merge it replaced.

``greedy_search`` keeps its beam sorted and merges each hop's new
candidates by counting ranks. The oracle below is the earlier search, which
deduplicated by one stable argsort by id and kept the best L by a second
stable argsort by distance every hop. The two must agree bit for bit on
every field, ties and repeated ids included."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distances import cdist2
from repro.core.graph_search import INF, _merge_beam, greedy_search

R = 18      # the served adjacency width: 16 pruned edges + 2 random


def _merge_beam_argsort(c_ids, c_d, c_exp, new_ids, new_d, L):
    ids = jnp.concatenate([c_ids, new_ids])
    ds = jnp.concatenate([c_d, new_d])
    exp = jnp.concatenate([c_exp, jnp.zeros(new_ids.shape, bool)])
    order = jnp.argsort(ids)
    sid = ids[order]
    dup = jnp.concatenate([jnp.zeros(1, bool), sid[1:] == sid[:-1]])
    ds = ds.at[order].set(jnp.where(dup, INF, ds[order]))
    keep = jnp.argsort(ds)[:L]
    return ids[keep], ds[keep], exp[keep]


@functools.partial(jax.jit, static_argnames=("L", "K"))
def _greedy_search_argsort(A, nbrs, n_nodes, entry, queries, *, L, K):
    m_cap = A.shape[0]
    max_hops = L + 32
    entries = jnp.broadcast_to(jnp.asarray(entry, jnp.int32),
                               (queries.shape[0],))

    def one(q, entry):
        d_entry = cdist2(q[None], A[entry][None])[0, 0]
        state = (jnp.full((L,), m_cap, jnp.int32).at[0].set(entry),
                 jnp.full((L,), INF).at[0].set(d_entry),
                 jnp.zeros((L,), bool),
                 jnp.zeros((m_cap + 1,), bool).at[entry].set(True),
                 jnp.full((max_hops,), m_cap, jnp.int32),
                 jnp.full((max_hops,), INF), jnp.zeros((), jnp.int32))

        def cond(state):
            _, c_d, c_exp, _, _, _, hop = state
            return (hop < max_hops) & jnp.any((~c_exp) & (c_d < INF))

        def body(state):
            c_ids, c_d, c_exp, visited, path, path_d, hop = state
            j = jnp.argmin(jnp.where(c_exp, INF, c_d))
            cur = c_ids[j]
            c_exp = c_exp.at[j].set(True)
            path = path.at[hop].set(cur)
            path_d = path_d.at[hop].set(c_d[j])
            nb = nbrs[jnp.minimum(cur, m_cap - 1)]
            nb = jnp.where(cur >= m_cap, m_cap, nb)
            valid = (nb < n_nodes) & ~visited[jnp.minimum(nb, m_cap)]
            nd = cdist2(q[None], A[jnp.minimum(nb, m_cap - 1)])[0]
            nd = jnp.where(valid, nd, INF)
            visited = visited.at[jnp.minimum(nb, m_cap)].set(True)
            c_ids, c_d, c_exp = _merge_beam_argsort(
                c_ids, c_d, c_exp, nb.astype(jnp.int32), nd, L)
            return c_ids, c_d, c_exp, visited, path, path_d, hop + 1

        c_ids, c_d, _, _, path, path_d, hops = jax.lax.while_loop(
            cond, body, state)
        order = jnp.argsort(c_d)[:K]
        return c_ids[order], c_d[order], path, path_d, hops

    return jax.vmap(one)(queries, entries)


def _graph(dtype, n=1500, m_cap=1600, d=8, n_queries=12, seed=0):
    """Random points and edges with the padding the served graph has: rows
    past ``n`` unused, sentinel (``m_cap``) pads, and rows that repeat ids.
    The uint8 points take four values per coordinate, so exact distance
    ties are everywhere."""
    rng = np.random.default_rng(seed)
    if dtype == "uint8":
        A = rng.integers(0, 4, (m_cap, d)).astype(np.uint8)
        q = rng.integers(0, 4, (n_queries, d)).astype(np.uint8)
    else:
        A = rng.standard_normal((m_cap, d)).astype(np.float32)
        q = rng.standard_normal((n_queries, d)).astype(np.float32)
    near = np.arange(m_cap)[:, None] + rng.integers(-40, 40, (m_cap, R - 2))
    nbrs = np.concatenate([np.clip(near, 0, n - 1),
                           rng.integers(0, n, (m_cap, 2))], 1)
    nbrs[::3, R - 3:] = nbrs[::3, :3]                  # repeated ids
    nbrs[rng.random((m_cap, R)) < 0.15] = m_cap        # sentinel pads
    nbrs[n:] = m_cap
    entries = rng.integers(0, n, n_queries).astype(np.int32)
    return (jnp.asarray(A), jnp.asarray(nbrs.astype(np.int32)), n,
            jnp.asarray(q), entries)


@pytest.mark.parametrize("per_query_entry", [False, True],
                         ids=["scalar-entry", "per-query-entry"])
@pytest.mark.parametrize("dtype", ["float32", "uint8"])
@pytest.mark.parametrize("K", ["10", "L"])
@pytest.mark.parametrize("L", [8, 32, 64, 256])
def test_greedy_search_matches_the_argsort_merge(L, K, dtype,
                                                 per_query_entry):
    A, nbrs, n, q, entries = _graph(dtype, seed=L)
    entry = jnp.asarray(entries) if per_query_entry else jnp.int32(7)
    k = L if K == "L" else 10
    got = greedy_search(A, nbrs, n, entry, q, L=L, K=k)
    want = _greedy_search_argsort(A, nbrs, n, entry, q, L=L, K=k)
    for field, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=field)
    # the search ran: the beam spread out past its entry
    assert int(np.asarray(got.n_hops).min()) > 1


def _beam(ids, ds, L=6, m_cap=99):
    pad = L - len(ids)
    return (jnp.asarray(list(ids) + [m_cap] * pad, jnp.int32),
            jnp.asarray(list(ds) + [float(INF)] * pad, jnp.float32),
            jnp.asarray([True] + [False] * (L - 1)))


@pytest.mark.parametrize("case", ["tie-after-beam", "repeat-keeps-first",
                                  "all-inf-new"])
def test_merge_beam_cases(case):
    L = 6
    c_ids, c_d, c_exp = _beam([10, 11, 12], [1.0, 2.0, 3.0], L)
    if case == "tie-after-beam":
        new_ids, new_d = [20, 21, 99], [2.0, 0.5, float(INF)]
        want_ids, want_d = [21, 10, 11, 20, 12, 99], [.5, 1, 2, 2, 3, INF]
    elif case == "repeat-keeps-first":
        new_ids, new_d = [30, 31, 30], [2.5, 4.0, 2.5]
        want_ids, want_d = [10, 11, 30, 12, 31, 99], [1, 2, 2.5, 3, 4, INF]
    else:
        new_ids, new_d = [99, 40, 41], [float(INF)] * 3
        want_ids, want_d = [10, 11, 12, 99, 99, 99], [1, 2, 3] + [INF] * 3
    args = (c_ids, c_d, c_exp, jnp.asarray(new_ids, jnp.int32),
            jnp.asarray(new_d, jnp.float32), L)
    ids, ds, exp = jax.jit(_merge_beam, static_argnums=5)(*args)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_array_equal(np.asarray(ds),
                                  np.asarray(want_d, np.float32))
    # the expanded flag travels with its entry
    np.testing.assert_array_equal(np.asarray(exp), np.asarray(ids) == 10)
    oracle = jax.jit(_merge_beam_argsort, static_argnums=5)(*args)
    for a, b in zip((ids, ds, exp), oracle):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

