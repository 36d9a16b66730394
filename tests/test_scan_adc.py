"""ScanStage.adc_select against the per-query reference it replaced: the
same refine lists, list for list, and byte-equal kernel inputs, over
seeded batches with redundant copies, repeated probes, lost objects and
empty queries; then end to end through search_pag with the kernel in
interpret mode, and the pool span's row counts."""
from __future__ import annotations

import types
from typing import Dict, List

import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.pq import PQCodebook, adc_lut_batch
from repro.dataplane import scan as scan_mod
from repro.dataplane.scan import ScanStage, dedup_first
from repro.kernels import ops

M = 8
K = 8


def reference_adc_select(stage, codebook, queries, probes_all, objs, pag,
                         rerank_k):
    """The per-query, per-row pooling and cover selection that
    ``adc_select`` computed before it was vectorised (spans left out)."""
    q_count = len(probes_all)
    cand_pids: List[np.ndarray] = []
    cand_codes: List[np.ndarray] = []
    cand_ids: List[np.ndarray] = []
    id_pids: List[Dict[int, List[int]]] = []
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
            keep = dedup_first(ids_c)
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
    rows, width = stage._shape(q_count, c_max)
    codes_pad = np.zeros((rows, width, m), np.uint8)
    pos_pad = np.full((rows, width), -1, np.int32)
    for qi in range(q_count):
        n = len(cand_pids[qi])
        if n:
            codes_pad[qi, :n] = cand_codes[qi]
            pos_pad[qi, :n] = np.arange(n, dtype=np.int32)
    luts = np.zeros((rows, m, 256), np.float32)
    luts[:q_count] = adc_lut_batch(codebook, np.asarray(queries, np.float32))
    _, pos = ops.pq_adc_masked(jnp.asarray(luts), jnp.asarray(codes_pad),
                               jnp.asarray(pos_pad), k=rerank_k,
                               block_c=stage.scan_block)
    pos = np.asarray(pos)[:q_count]
    refine_all: List[List[int]] = []
    for qi in range(q_count):
        chosen: List[int] = []
        chosen_set: set = set()
        for p in pos[qi]:
            if p < 0:
                continue
            copies = id_pids[qi].get(int(cand_ids[qi][p]))
            if copies is None:
                copies = [int(cand_pids[qi][p])]
            if chosen_set.intersection(copies):
                continue
            pid = int(cand_pids[qi][p])
            chosen.append(pid)
            chosen_set.add(pid)
        refine_all.append(chosen)
    return refine_all


def _numpy_adc_topk(luts, codes, ids, k, block_c=256):
    """ADC distances and a stable top-k in numpy, for the unit cases."""
    luts, codes, ids = map(np.asarray, (luts, codes, ids))
    m = luts.shape[1]
    d2 = luts[np.arange(len(luts))[:, None, None], np.arange(m),
              codes.astype(np.int64)].sum(-1)
    d2 = np.where(ids >= 0, d2, np.float32(3.4e38))
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    out_i = np.take_along_axis(ids, order, 1)
    out_d = np.take_along_axis(d2, order, 1)
    if out_i.shape[1] < k:
        pad = k - out_i.shape[1]
        out_i = np.pad(out_i, ((0, 0), (0, pad)), constant_values=-1)
        out_d = np.pad(out_d, ((0, 0), (0, pad)), constant_values=3.4e38)
    return out_d, out_i


@pytest.fixture
def capture(monkeypatch):
    """Records every ADC launch's (luts, codes, positions) as numpy."""
    calls = []

    def fake(luts, codes, ids, k, block_c=256):
        calls.append(tuple(np.asarray(a) for a in (luts, codes, ids)))
        return _numpy_adc_topk(luts, codes, ids, k, block_c)

    monkeypatch.setattr(ops, "pq_adc_masked", fake)
    return calls


def _batch(case: str, seed: int):
    """A seeded batch: (codebook, queries, probes_all, objs, pag)."""
    rng = np.random.default_rng(seed)
    n_part, cap, q_count, d_sub = 40, 12, 9, 2
    universe = 25 if case == "multi_copy_top" else 120
    counts = rng.integers(1, cap + 1, n_part)
    plist = np.full((n_part, cap), -1, np.int32)
    for pid, c in enumerate(counts):      # distinct ids within a partition
        plist[pid, :c] = rng.choice(universe, c, replace=False)
    if case == "invalid_ids":
        plist[rng.integers(0, n_part, 10), 0] = -1
    objs = {pid: rng.integers(0, 256, (c, M), dtype=np.uint8)
            for pid, c in enumerate(counts)}
    probes_all = [rng.choice(n_part, rng.integers(3, 15),
                             replace=False).tolist()
                  for _ in range(q_count)]
    if case == "pid_twice":
        for p in probes_all:
            p.insert(rng.integers(0, len(p) + 1), p[0])
    elif case == "lost":
        for pid in rng.choice(n_part, 12, replace=False).tolist():
            del objs[pid]
    elif case == "empty_query":
        probes_all[2] = []
        for pid in probes_all[6]:
            objs.pop(pid, None)
    elif case == "all_empty":
        probes_all = [[] for _ in range(q_count)]
    cb = PQCodebook(rng.standard_normal((M, 256, d_sub)).astype(np.float32),
                    M, M * d_sub)
    queries = rng.standard_normal((q_count, M * d_sub)).astype(np.float32)
    pag = types.SimpleNamespace(plist=plist)
    return cb, queries, probes_all, objs, pag


CASES = ["redundant", "pid_twice", "lost", "empty_query", "invalid_ids",
         "multi_copy_top", "all_empty"]


@pytest.mark.parametrize("seed", [0, 1, 2 ** 33 + 5])
@pytest.mark.parametrize("case", CASES)
def test_adc_select_matches_the_per_query_reference(capture, case, seed):
    cb, queries, probes_all, objs, pag = _batch(case, seed)
    stage = ScanStage(scan_block=16, pad_rows=12)
    want = reference_adc_select(stage, cb, queries, probes_all, objs, pag, K)
    got = stage.adc_select(cb, queries, probes_all, objs, pag, K)
    assert got == want
    assert all(type(p) is int for r in got for p in r)
    if case == "all_empty":
        assert got == [[]] * len(probes_all) and capture == []
        return
    (ref_in, new_in) = capture
    for a, b in zip(ref_in, new_in):    # luts, codes_pad, pos_pad
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    if case == "empty_query":
        assert got[2] == [] and got[6] == []
    if case == "multi_copy_top":
        # some ADC top holds a candidate covered by an earlier choice
        pos = _numpy_adc_topk(*new_in, K)[1][:len(got)]
        assert any(len(r) < (p >= 0).sum() for r, p in zip(got, pos))


# ----------------------------------------------------- end to end, n=1,200
@pytest.fixture(scope="module")
def deployment():
    from repro.core.pag import build_pag
    from repro.core.search import write_partitions
    from repro.data.vectors import make_dataset
    from repro.storage.simulator import ObjectStore, StorageConfig

    ds = make_dataset("clustered", n=1200, d=32, n_queries=16, seed=3)
    pag = build_pag(ds.base, p=0.2, lam=6.0, redundancy=4, seed=0)
    store = ObjectStore(StorageConfig.preset("dfs", seed=1))
    write_partitions(pag, ds.base, store, n_shards=4, compression="pq",
                     pq_m=8)
    return ds, pag, store


def _search(deployment, monkeypatch, adc_select):
    from repro.core.search import SearchConfig, search_pag

    ds, pag, store = deployment
    launches, refines = [], []
    real = ops.pq_adc_masked

    def kernel(luts, codes, ids, **kw):
        launches.append(tuple(np.asarray(a) for a in (luts, codes, ids)))
        return real(luts, codes, ids, **kw)

    def select(self, *a):
        refines.append(adc_select(self, *a))
        return refines[-1]

    monkeypatch.setattr(ops, "pq_adc_masked", kernel)
    monkeypatch.setattr(ScanStage, "adc_select", select)
    cfg = SearchConfig(L=32, k=10, n_probe_max=16, compression="pq",
                       rerank_k=16)
    ids, d2, _ = search_pag(pag, ds.d, ds.queries, store, cfg, n_shards=4)
    monkeypatch.undo()
    return ids, d2, launches, refines


def test_served_pq_search_matches_the_reference_end_to_end(deployment,
                                                          monkeypatch):
    """The Pallas kernel (interpret mode here) gets the same inputs, the
    refine wave the same lists, and search_pag the same answers."""
    new = _search(deployment, monkeypatch, ScanStage.adc_select)
    ref = _search(deployment, monkeypatch, reference_adc_select)
    np.testing.assert_array_equal(new[0], ref[0])
    np.testing.assert_array_equal(new[1], ref[1])
    assert new[3] == ref[3] and any(map(len, new[3][0]))
    assert len(new[2]) == len(ref[2]) == 1
    for a, b in zip(new[2][0], ref[2][0]):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_adc_pool_span_counts_rows_before_and_after_dedup(deployment):
    """``anns/scan.adc_pool`` carries the rows pooled and the rows kept
    after dedup; the kept rows are the ADC launch's filled slots."""
    from repro.core.search import SearchConfig, search_pag
    from repro.obs import Tracer, observe

    ds, pag, store = deployment
    tracer = Tracer()
    cfg = SearchConfig(L=32, k=10, n_probe_max=16, compression="pq",
                       rerank_k=16)
    with observe(tracer=tracer):
        search_pag(pag, ds.d, ds.queries, store, cfg, n_shards=4)
    (pool,) = [s.args for s in tracer.spans
               if s.name == "anns/scan.adc_pool"]
    (launch,) = [s.args for s in tracer.spans
                 if s.name == "anns/scan.adc_launch"]
    assert pool["rows"] >= pool["kept"] > 0
    assert pool["rows"] > pool["kept"]     # redundancy 4: copies pooled
    assert pool["kept"] == launch["filled"]


def test_adc_pool_keeps_every_row_sorted_by_query_and_id():
    """Every pooled row stays among the sorted keys (a candidate's copies
    are one slice of them); the kept rows are the pool the kernel gets."""
    cb, queries, probes_all, objs, pag = _batch("redundant", 4)
    pool = scan_mod._AdcPool(probes_all, objs, pag.plist, M)
    assert pool.n_rows == sum(objs[p].shape[0] for r in probes_all
                              for p in r)
    assert len(pool.keys) == pool.n_rows     # every id valid here
    assert np.all(np.diff(pool.keys) >= 0)
    assert pool.counts.sum() == len(pool.ids) == len(pool.codes)
