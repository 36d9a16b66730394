"""Scan stage of the staged data plane: the Pallas kernel launches.

``ScanStage`` wraps the two masked ragged-pool launches — ``l2_topk``
(exact distance/top-k over the pooled candidates) and ``pq_adc`` (ADC
scoring of pooled PQ codes + cover-aware refine-partition selection) —
behind one object that owns padding, id bookkeeping, and the dedup rule
for redundant copies (Def 5). Both engines and the benchmarks go through
this stage; nothing else in the tree calls ``kernels.ops`` for the query
path. Each step is a host span (``anns/scan.*``); a launch span covers
the host->device copy, the kernel and the pull of its result, with the
bytes copied (``h2d_bytes``), the launch's slots (rows x pool width) and
the slots that hold a real candidate (``filled``).
"""
from __future__ import annotations

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
        masked l2_topk kernel. Returns (ids [Q, k] int64, d2 [Q, k])."""
        q_count, d = queries.shape
        c_max = max((len(p) for p in pool_ids), default=0)
        if c_max == 0:
            return (np.full((q_count, k), -1, np.int64),
                    np.full((q_count, k), INF, np.float32))
        rows, width = self._shape(q_count, c_max)
        with host_span("scan.topk_pad"):
            q_pad = np.zeros((rows, d), np.float32)
            q_pad[:q_count] = queries
            ids_pad = np.full((rows, width), -1, np.int32)
            vecs_pad = np.zeros((rows, width, d), np.float32)
            for qi in range(q_count):
                n = len(pool_ids[qi])
                if n:
                    ids_pad[qi, :n] = pool_ids[qi]
                    vecs_pad[qi, :n] = pool_vecs[qi]
        with host_span("scan.topk_launch",
                       h2d_bytes=q_pad.nbytes + vecs_pad.nbytes
                       + ids_pad.nbytes, slots=rows * width,
                       filled=sum(map(len, pool_ids))):
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
        cand_pids: List[np.ndarray] = []
        cand_codes: List[np.ndarray] = []
        cand_ids: List[np.ndarray] = []
        id_pids: List[Dict[int, List[int]]] = []  # id -> probed pids
        with host_span("scan.adc_pool"):
            for qi in range(q_count):
                ids_l, pids_l, codes_l = [], [], []
                for pid in probes_all[qi]:
                    codes = objs.get(pid)
                    if codes is None:
                        continue
                    cnt = codes.shape[0]
                    ids_l.append(pag.plist[pid, :cnt].astype(np.int64))
                    pids_l.append(np.full(cnt, pid, np.int32))
                    codes_l.append(codes)
                if ids_l:
                    ids_c = np.concatenate(ids_l)
                    pids_c = np.concatenate(pids_l)
                    keep = dedup_first(ids_c)  # redundant copies score once
                    cand_pids.append(pids_c[keep])
                    cand_codes.append(np.concatenate(codes_l)[keep])
                    cand_ids.append(ids_c[keep])
                    by_id: Dict[int, List[int]] = {}
                    for i, cid in zip(pids_c, ids_c):
                        by_id.setdefault(int(cid), []).append(int(i))
                    id_pids.append(by_id)
                else:
                    cand_pids.append(np.zeros(0, np.int32))
                    cand_codes.append(np.zeros((0, codebook.M), np.uint8))
                    cand_ids.append(np.zeros(0, np.int64))
                    id_pids.append({})

        c_max = max((len(p) for p in cand_pids), default=0)
        if c_max == 0:
            return [[] for _ in range(q_count)]
        m = codebook.M
        rows, width = self._shape(q_count, c_max)
        with host_span("scan.adc_lut"):
            codes_pad = np.zeros((rows, width, m), np.uint8)
            pos_pad = np.full((rows, width), -1, np.int32)
            for qi in range(q_count):
                n = len(cand_pids[qi])
                if n:
                    codes_pad[qi, :n] = cand_codes[qi]
                    pos_pad[qi, :n] = np.arange(n, dtype=np.int32)
            luts = np.zeros((rows, m, 256), np.float32)
            luts[:q_count] = adc_lut_batch(codebook,
                                           np.asarray(queries, np.float32))
        with host_span("scan.adc_launch",
                       h2d_bytes=luts.nbytes + codes_pad.nbytes
                       + pos_pad.nbytes, slots=rows * width,
                       filled=sum(map(len, cand_pids))):
            _, pos = ops.pq_adc_masked(
                jnp.asarray(luts), jnp.asarray(codes_pad),
                jnp.asarray(pos_pad), k=rerank_k, block_c=self.scan_block)
            pos = np.asarray(pos)[:q_count]

        refine_all: List[List[int]] = []
        with host_span("scan.cover_select"):
            for qi in range(q_count):
                chosen: List[int] = []
                chosen_set: set = set()
                for p in pos[qi]:
                    if p < 0:
                        continue
                    copies = id_pids[qi].get(int(cand_ids[qi][p]))
                    if copies is None:  # defensive: scored row has copies
                        copies = [int(cand_pids[qi][p])]
                    if chosen_set.intersection(copies):
                        continue  # a selected partition holds a copy
                    pid = int(cand_pids[qi][p])
                    chosen.append(pid)
                    chosen_set.add(pid)
                refine_all.append(chosen)
        return refine_all
