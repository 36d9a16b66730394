"""A plain numpy reference for ``search_pag``'s data plane.

It runs the same graph phase (``greedy_search``) and APP replay
(``probe_orders``) as ``search_pag`` — both have their own reference
tests — and from there on shares nothing with the program: no store, no
``FetchPlan``, no ``KeySpace``, no ``ScanStage``, no kernel. For each
query it pools the beam's aggregation points and the rows of every probed
partition, reads their vectors straight from the base array
(``x[pag.plist[pid, :pcount[pid]]]``), dedups by original id and takes
the exact top-k in float64.

The compressed plane (``compression="pq"``) ranks the probed rows by ADC
(``baselines.pq.adc_lut_batch`` over the codebook ``write_partitions``
returned, codes from ``encode_pq``), takes the ADC-top ``rerank_k``,
covers them with partitions in ADC rank order (a candidate none of whose
probed copies lies in a chosen partition adds the partition of its first
copy), and reranks the beam plus the rows of the chosen partitions
exactly from ``x``: the partitions the exact refine wave fetches.
"""
from __future__ import annotations

from typing import List, NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.baselines.pq import adc_lut_batch, encode_pq
from repro.core.graph_search import greedy_search
from repro.dataplane.plan import probe_orders

PAD_D2 = np.float32(3.4e38)   # distance of a row's padding (id -1)


class Reference(NamedTuple):
    ids: np.ndarray             # [Q, k] original ids, -1 pads
    d2: np.ndarray              # [Q, k] float64 exact squared distances
    pools: List[np.ndarray]     # per query: every candidate id reranked


def _partition(pag, pid: int) -> np.ndarray:
    return pag.plist[pid, :int(pag.pcount[pid])].astype(np.int64)


def _graph_phase(pag, queries: np.ndarray, cfg):
    """Per query: the original ids of the beam's aggregation points and
    the probe order APP replays from the traversal path."""
    pg = pag.pg
    a, nbrs, n_nodes, entry = pg.device_arrays()
    res = greedy_search(a, nbrs, n_nodes, entry, jnp.asarray(queries),
                        L=cfg.L, K=cfg.L)
    beam_ids, beam_d2 = np.asarray(res.ids), np.asarray(res.dists)
    probes = probe_orders(pag, np.asarray(res.path),
                          np.asarray(res.path_dists), np.asarray(res.n_hops),
                          cfg.rho, cfg.n_probe_max)
    beams = []
    for qi in range(len(queries)):
        ok = (beam_ids[qi] < pg.n_nodes) & (beam_d2[qi] < PAD_D2)
        src = pag.node_src[beam_ids[qi][ok]].astype(np.int64)
        beams.append(src[src >= 0])
    return beams, probes


def _adc_cover(pag, probes: List[int], lut: np.ndarray, codes: np.ndarray,
               rerank_k: int) -> List[int]:
    """The partitions that cover one query's ADC-top ``rerank_k``."""
    rows = [_partition(pag, pid) for pid in probes]
    if not rows:
        return []
    row_ids = np.concatenate(rows)
    row_pid = np.repeat(probes, list(map(len, rows)))
    _, first = np.unique(row_ids, return_index=True)
    first.sort()                        # pool order: first copy of each id
    cand, cand_pid = row_ids[first], row_pid[first]
    adc = np.zeros(len(cand), np.float32)
    for m in range(lut.shape[0]):       # subspace by subspace, in float32
        adc = adc + lut[m, codes[cand, m]]
    copies = {}
    for pid, ids in zip(probes, rows):
        for i in ids.tolist():
            copies.setdefault(i, set()).add(pid)
    chosen: List[int] = []
    for c in np.argsort(adc, kind="stable")[:rerank_k]:
        if copies[int(cand[c])].isdisjoint(chosen):
            chosen.append(int(cand_pid[c]))
    return chosen


def reference_search(pag, x: np.ndarray, queries: np.ndarray, cfg,
                     codebook=None) -> Reference:
    """Exact answers of the data plane for ``cfg`` (a ``SearchConfig``);
    ``codebook`` is what ``write_partitions`` returned, needed when
    ``cfg.compression == "pq"``."""
    queries = np.asarray(queries, pag.pg.dtype)
    beams, probes = _graph_phase(pag, queries, cfg)
    pq = cfg.compression == "pq"
    if pq:
        codes = encode_pq(codebook, np.asarray(x, np.float32))
        luts = adc_lut_batch(codebook, np.asarray(queries, np.float32))
    q_count, k = len(queries), cfg.k
    ids = np.full((q_count, k), -1, np.int64)
    d2 = np.full((q_count, k), PAD_D2, np.float64)
    pools = []
    for qi in range(q_count):
        parts = (_adc_cover(pag, probes[qi], luts[qi], codes, cfg.rerank_k)
                 if pq else probes[qi])
        pool = np.unique(np.concatenate(
            [beams[qi]] + [_partition(pag, pid) for pid in parts]))
        diff = np.asarray(x[pool], np.float64) - queries[qi].astype(
            np.float64)
        dist = (diff * diff).sum(axis=1)
        top = np.argsort(dist, kind="stable")[:k]
        ids[qi, :len(top)] = pool[top]
        d2[qi, :len(top)] = dist[top]
        pools.append(pool)
    return Reference(ids, d2, pools)


def assert_matches_reference(ids: np.ndarray, d2: np.ndarray,
                             ref: Reference, x: np.ndarray,
                             queries: np.ndarray, rows=None,
                             rtol: float = 1e-5):
    """``search_pag``'s answers against the reference, row by row (all
    rows, or those in ``rows``): distances within ``rtol``, ids equal.
    Where the two disagree on an id, either order passes only if the
    returned id is a reference candidate whose exact distance ties the
    reference's at that place within ``rtol``."""
    rows = range(len(ids)) if rows is None else rows
    for qi in rows:
        np.testing.assert_allclose(d2[qi], ref.d2[qi], rtol=rtol,
                                   err_msg=f"query {qi}")
        got = ids[qi][ids[qi] >= 0]
        assert len(set(got.tolist())) == len(got), f"query {qi}: repeats"
        for j in np.flatnonzero(ids[qi] != ref.ids[qi]):
            i = int(ids[qi, j])
            assert i in ref.pools[qi], (qi, j, i)
            diff = np.asarray(x[i], np.float64) - np.asarray(
                queries[qi], np.float64)
            np.testing.assert_allclose((diff * diff).sum(), ref.d2[qi, j],
                                       rtol=rtol, err_msg=f"{qi}, {j}")
