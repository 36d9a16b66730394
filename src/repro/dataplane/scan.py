"""Scan stage of the staged data plane: the Pallas kernel launches.

``ScanStage`` wraps the two masked ragged-pool launches — ``l2_topk``
(exact distance/top-k over the pooled candidates) and ``pq_adc`` (ADC
scoring of pooled PQ codes + cover-aware refine-partition selection) —
behind one object that owns padding, id bookkeeping, and the dedup rule
for redundant copies (Def 5). ``search_pag`` and the benchmarks go
through this stage; nothing else in the tree calls ``kernels.ops`` for
the query path. Each step is a host span (``anns/scan.*``); a launch
span covers the host->device copy, the kernel and the pull of its
result, with the bytes copied (``h2d_bytes``), the launch's slots (rows
x pool width) and the slots that hold a real candidate (``filled``); the
exact launch also gives the bytes of one pooled element (``itemsize``:
the base's type, 1 for uint8 / int8); the ADC pool span counts the rows
pooled (``rows``) and those kept after dedup (``kept``).
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs import host_span

INF = np.float32(3.4e38)
ID_SENTINEL = 2 ** 62   # invalid-id marker used during dedup


def dedup_first(ids: np.ndarray) -> np.ndarray:
    """Keep-mask of the first occurrence of each id (redundant copies,
    Def 5). Invalid ids (< 0) map to the ID_SENTINEL and are dropped."""
    ids = np.where(ids >= 0, ids, ID_SENTINEL)
    _, first = np.unique(ids, return_index=True)
    mask = np.zeros(len(ids), bool)
    mask[first] = True
    mask &= ids < ID_SENTINEL
    return mask


class _AdcPool:
    """One batch's ADC candidate pool in flat arrays. Rows run query by
    query, each query's probes in its order, each fetched object row by
    row: the order of a per-query concatenation. A query keeps the first
    row of each id (``dedup_first``'s rule, ids < 0 dropped); every
    pooled row stays in ``keys``/``key_pids``, sorted by (query, id), so
    a candidate's copies are one slice of them."""

    def __init__(self, probes_all: List[List[int]],
                 objs: Dict[int, np.ndarray], plist: np.ndarray, m: int):
        q_count = len(probes_all)
        n_probes = np.fromiter(map(len, probes_all), np.int64, q_count)
        probe_pid = np.fromiter(itertools.chain.from_iterable(probes_all),
                                np.int64, int(n_probes.sum()))
        probe_q = np.repeat(np.arange(q_count), n_probes)
        # each distinct object once: a missing one brings no rows
        uniq, probe_obj = np.unique(probe_pid, return_inverse=True)
        got = [objs.get(pid) for pid in uniq.tolist()]
        obj_rows = np.array([0 if o is None else o.shape[0] for o in got],
                            np.int64)
        present = [o for o in got if o is not None]
        all_codes = (np.concatenate(present) if present
                     else np.zeros((0, m), np.uint8))
        # expand each probe to its object's rows
        cnt = obj_rows[probe_obj]
        self.n_rows = int(cnt.sum())
        row_probe = np.repeat(np.arange(len(cnt)), cnt)
        within = np.arange(self.n_rows) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        row_q = probe_q[row_probe]
        row_pid = probe_pid[row_probe]
        row_id = plist[row_pid, within].astype(np.int64)
        row_code = (np.cumsum(obj_rows) - obj_rows)[probe_obj][row_probe] \
            + within
        # dedup per query: one stable sort on (query, id)
        valid = np.flatnonzero(row_id >= 0)
        self.span = int(row_id.max(initial=-1)) + 1
        key = row_q[valid] * self.span + row_id[valid]
        order = np.argsort(key, kind="stable")
        self.keys = key[order]
        self.key_pids = row_pid[valid][order]
        first = np.ones(len(order), bool)
        first[1:] = self.keys[1:] != self.keys[:-1]
        kept = np.sort(valid[order[first]])
        self.q = row_q[kept]
        self.ids = row_id[kept]
        self.pids = row_pid[kept]
        self.codes = all_codes[row_code[kept]]
        self.counts = np.bincount(self.q, minlength=q_count)
        self.start = np.cumsum(self.counts) - self.counts
        self.rank = (np.arange(len(kept)) - self.start[self.q]).astype(
            np.int32)

    def cover(self, pos: np.ndarray) -> List[List[int]]:
        """Greedy cover of each query's ADC top (``pos`` [Q, k], ranks
        into the query's pool, -1 empty), in rank order: a candidate
        none of whose copies lies in a chosen partition adds its first
        copy's partition."""
        qs, cols = np.nonzero(pos >= 0)
        rows = self.start[qs] + pos[qs, cols]
        keys = qs * self.span + self.ids[rows]
        lo = np.searchsorted(self.keys, keys, "left")
        n = np.searchsorted(self.keys, keys, "right") - lo
        ends = np.cumsum(n)
        # the copies of every candidate, candidate after candidate
        copies = self.key_pids[np.repeat(lo - ends + n, n)
                               + np.arange(n.sum())].tolist()
        refine: List[List[int]] = [[] for _ in range(len(pos))]
        chosen: List[set] = [set() for _ in range(len(pos))]
        a = 0
        for qi, pid, b in zip(qs.tolist(), self.pids[rows].tolist(),
                              ends.tolist()):
            if chosen[qi].isdisjoint(copies[a:b]):
                refine[qi].append(pid)
                chosen[qi].add(pid)
            a = b
        return refine


class ScanStage:
    """The compute stage: one masked Pallas launch per scan kind."""

    def __init__(self, scan_block: int = 256, pad_rows: int = 0):
        self.scan_block = scan_block
        self.pad_rows = pad_rows    # launch at least this many query rows

    def _shape(self, q_count: int, c_max: int) -> Tuple[int, int]:
        """Launch shape (rows, pool width): rows padded to ``pad_rows``,
        width rounded up to a multiple of ``scan_block``, so the jitted
        kernels see few distinct shapes across micro-batches. Padded rows
        and columns carry id -1 and never reach a result."""
        width = -(-c_max // self.scan_block) * self.scan_block
        return max(q_count, self.pad_rows), width

    # ---------------------------------------------------------- exact topk
    def topk(self, queries: np.ndarray, pool_ids: List[np.ndarray],
             pool_vecs: List[np.ndarray], k: int
             ) -> Tuple[np.ndarray, np.ndarray]:
        """One vectorized distance/top-k pass over every query's candidate
        pool (ragged rows padded with id -1), routed through the Pallas
        masked l2_topk kernel. Queries and pools come, and are padded and
        launched, in the base's element type (float32, uint8 or int8).
        Returns (ids [Q, k] int64, d2 [Q, k])."""
        q_count, d = queries.shape
        c_max = max((len(p) for p in pool_ids), default=0)
        if c_max == 0:
            return (np.full((q_count, k), -1, np.int64),
                    np.full((q_count, k), INF, np.float32))
        rows, width = self._shape(q_count, c_max)
        with host_span("scan.topk_pad"):
            q_pad = np.zeros((rows, d), queries.dtype)
            q_pad[:q_count] = queries
            ids_pad = np.full((rows, width), -1, np.int32)
            vecs_pad = np.zeros((rows, width, d), queries.dtype)
            for qi in range(q_count):
                n = len(pool_ids[qi])
                if n:
                    ids_pad[qi, :n] = pool_ids[qi]
                    vecs_pad[qi, :n] = pool_vecs[qi]
        with host_span("scan.topk_launch",
                       h2d_bytes=q_pad.nbytes + vecs_pad.nbytes
                       + ids_pad.nbytes, slots=rows * width,
                       filled=sum(map(len, pool_ids)),
                       itemsize=vecs_pad.itemsize):
            d2, ids = ops.l2_topk_masked(
                jnp.asarray(q_pad), jnp.asarray(vecs_pad),
                jnp.asarray(ids_pad), k=k, block_c=self.scan_block)
            return (np.asarray(ids)[:q_count].astype(np.int64),
                    np.asarray(d2)[:q_count])

    # ------------------------------------------------------------ ADC pass
    def adc_select(self, codebook, queries: np.ndarray,
                   probes_all: List[List[int]],
                   objs: Dict[int, np.ndarray], pag, rerank_k: int
                   ) -> List[List[int]]:
        """The ADC stage of the compressed plane: pool every query's
        fetched code objects (rows mapped to original ids via the
        in-memory ``pag.plist``, deduped like the exact pool), score ALL
        pools in one masked Pallas launch, and return, per query, the
        partitions holding its ADC-top ``rerank_k`` candidates (ordered
        by ADC rank) — the exact refine wave's fetch list. Redundant
        copies (Def 5) make the partition choice a covering problem: a
        candidate counts as covered by ANY already-selected partition
        holding one of its copies, so the refine wave fetches the fewest
        partitions that cover the ADC top."""
        from repro.baselines.pq import adc_lut_batch
        q_count = len(probes_all)
        with host_span("scan.adc_pool") as sp:
            pool = _AdcPool(probes_all, objs, pag.plist, codebook.M)
            sp.set(rows=pool.n_rows, kept=len(pool.ids))
        c_max = int(pool.counts.max(initial=0))
        if c_max == 0:
            return [[] for _ in range(q_count)]
        m = codebook.M
        rows, width = self._shape(q_count, c_max)
        with host_span("scan.adc_lut"):
            codes_pad = np.zeros((rows, width, m), np.uint8)
            pos_pad = np.full((rows, width), -1, np.int32)
            codes_pad[pool.q, pool.rank] = pool.codes
            pos_pad[pool.q, pool.rank] = pool.rank
            luts = np.zeros((rows, m, 256), np.float32)
            luts[:q_count] = adc_lut_batch(codebook,
                                           np.asarray(queries, np.float32))
        with host_span("scan.adc_launch",
                       h2d_bytes=luts.nbytes + codes_pad.nbytes
                       + pos_pad.nbytes, slots=rows * width,
                       filled=len(pool.ids)):
            _, pos = ops.pq_adc_masked(
                jnp.asarray(luts), jnp.asarray(codes_pad),
                jnp.asarray(pos_pad), k=rerank_k, block_c=self.scan_block)
            pos = np.asarray(pos)[:q_count]

        with host_span("scan.cover_select"):
            return pool.cover(pos)
