#!/usr/bin/env python3
"""Bring-up smoke of the served search path on one TPU chip.

Drives the path a user calls, once per data plane (``compression="none"``
and ``"pq"``):

    AnnsFrontend -> ShardedServing.search -> search_pag
        -> graph phase (greedy_search, jitted)
        -> ScanStage: pq_adc_masked / l2_topk_masked (Pallas, Mosaic)

on a DEEP-1B-shaped deployment (big-ann-benchmarks NeurIPS'21 DEEP: f32,
d=96, squared L2, k=10). The base vectors are generated from ``--seed``;
the partitions live in the simulated ``dfs`` object store (4 simulated
shards, one process). Storage latency is simulated and every number drawn
from it is printed under a ``modelled_`` name; every other time is the
host's wall clock around a result forced to the host.

    python3 chip_smoke.py [--n 200000] [--seed 0]

Each phase prints one JSON line. The last line of a run that passed is
``{"ok": true, "device": {...}}``. Without a TPU, or when any phase fails,
the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.distributed import ShardedServing  # noqa: E402
from repro.core.pag import PAG, build_pag  # noqa: E402
from repro.core.search import SearchConfig, write_partitions  # noqa: E402
from repro.data.vectors import VectorDataset, make_dataset, \
    recall_at_k  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.serving.engine import AnnsFrontend  # noqa: E402
from repro.storage.simulator import ObjectStore, StorageConfig  # noqa: E402

D = 96                  # DEEP-1B vector width
K = 10                  # recall@10
N_QUERIES = 1024
BATCH = 64              # AnnsFrontend micro-batch
N_SHARDS = 4            # simulated storage shards
PQ_M = 16               # PQ subspaces (d_sub = 6)
RERANK_K = 32           # ADC-top candidates refined exactly
RECALL_FLOOR = 0.65     # float plane, at SEARCH below (see PERF.md)
SEARCH = dict(L=512, k=K, n_probe_max=512, rerank_k=RERANK_K, pq_m=PQ_M)
BUILD = dict(p=0.2, lam=6.0, redundancy=4)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


@dataclasses.dataclass
class Deployment:
    ds: VectorDataset
    pag: PAG
    store: ObjectStore
    data_wall_s: float
    build_wall_s: float
    write_wall_s: float


def build(n: int, seed: int, n_queries: int = N_QUERIES) -> Deployment:
    """Generate the base and queries, build the PAG and write both
    payload formats (float residuals + PQ codes) to the simulated store.
    Ground truth is exact kNN (``brute_force_knn``)."""
    t0 = time.perf_counter()
    ds = make_dataset("clustered", n=n, d=D, n_queries=n_queries, k_gt=K,
                      seed=seed)
    t1 = time.perf_counter()
    pag = build_pag(ds.base, seed=seed, **BUILD)
    t2 = time.perf_counter()
    store = ObjectStore(StorageConfig.preset("dfs", seed=seed))
    write_partitions(pag, ds.base, store, n_shards=N_SHARDS,
                     compression="pq", pq_m=PQ_M, pq_seed=seed)
    t3 = time.perf_counter()
    return Deployment(ds, pag, store, t1 - t0, t2 - t1, t3 - t2)


@contextlib.contextmanager
def count_compiles():
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) inside the block: ``with count_compiles() as c``,
    then ``c[0]``."""
    count = [0]

    def listener(event, duration_s, **_):
        if event == COMPILE_EVENT:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@contextlib.contextmanager
def capture_launches():
    """Records the arguments of the last masked-kernel launch of each
    kind that ``ScanStage`` makes inside the block (the real padded
    pools of one batch), keyed ``"l2"`` / ``"pq"``."""
    seen: Dict[str, tuple] = {}
    orig_l2, orig_pq = ops.l2_topk_masked, ops.pq_adc_masked

    def l2(q, pools, ids, k=10, block_c=256, interpret=None):
        seen["l2"] = (q, pools, ids, k, block_c)
        return orig_l2(q, pools, ids, k=k, block_c=block_c,
                       interpret=interpret)

    def pq(luts, codes, ids, k=10, block_c=256, interpret=None):
        seen["pq"] = (luts, codes, ids, k, block_c)
        return orig_pq(luts, codes, ids, k=k, block_c=block_c,
                       interpret=interpret)

    ops.l2_topk_masked, ops.pq_adc_masked = l2, pq
    try:
        yield seen
    finally:
        ops.l2_topk_masked, ops.pq_adc_masked = orig_l2, orig_pq


@dataclasses.dataclass
class ServeResult:
    ids: np.ndarray                 # [Q, K] original ids
    batch_wall_s: List[float]       # host clock per micro-batch
    compiles: int                   # executables built in the timed pass
    modelled_latency_s: np.ndarray  # event clock (simulated storage)
    launches: Dict[str, tuple]      # last batch's kernel arguments


def _run_stream(fe: AnnsFrontend, queries: np.ndarray) -> List[float]:
    """Submit the queries in micro-batches; each batch flushes on its
    last submit and its results are numpy arrays (forced to the host)."""
    times = []
    for s in range(0, len(queries), fe.max_batch):
        t0 = time.perf_counter()
        for q in queries[s:s + fe.max_batch]:
            fe.submit(q)
        fe.flush()
        times.append(time.perf_counter() - t0)
    return times


def serve(dep: Deployment, plane: str) -> ServeResult:
    """Warm-up pass over the query stream, then the timed pass over the
    same stream (so it uses only shapes the warm-up compiled)."""
    srv = ShardedServing(pag=dep.pag, store=dep.store, n_shards=N_SHARDS,
                         dim=D)
    cfg = SearchConfig(compression=plane, **SEARCH)
    queries = dep.ds.queries
    _run_stream(AnnsFrontend(srv, cfg, max_batch=BATCH), queries)
    fe = AnnsFrontend(srv, cfg, max_batch=BATCH)
    with count_compiles() as compiles, capture_launches() as launches:
        times = _run_stream(fe, queries)
    tickets = sorted(fe.results)
    ids = np.stack([fe.results[t][0] for t in tickets])
    lat = np.asarray([fe.results[t][2] for t in tickets])
    return ServeResult(ids, times, compiles[0], lat, dict(launches))


def _same_topk(d_k, i_k, d_r, i_r) -> Dict[str, int]:
    """Kernel vs oracle top-k: sorted distances agree, and id sets are
    equal except for entries tied (within tolerance) with the row's
    k-th distance. Returns counts; raises AssertionError on a mismatch."""
    d_k, i_k = np.asarray(d_k), np.asarray(i_k)
    d_r, i_r = np.asarray(d_r), np.asarray(i_r)
    np.testing.assert_allclose(d_k, d_r, rtol=1e-4, atol=1e-4)
    exact = tied = 0
    for row in range(len(i_k)):
        a, b = set(i_k[row].tolist()), set(i_r[row].tolist())
        if a == b:
            exact += 1
            continue
        edge = d_r[row, -1]
        odd = [d for d, i in zip(d_k[row], i_k[row]) if i not in b] + \
              [d for d, i in zip(d_r[row], i_r[row]) if i not in a]
        assert np.allclose(odd, edge, rtol=1e-4, atol=1e-4), (
            f"row {row}: id sets differ beyond ties at d2={edge}")
        tied += 1
    return {"rows_exact": exact, "rows_tied": tied}


def check_parity(launches: Dict[str, tuple]) -> Dict[str, dict]:
    """Re-run each captured launch through ``ops`` and compare it with
    its ``kernels/ref.py`` oracle (full f32 matmul precision)."""
    out = {}
    q, pools, ids, k, block_c = launches["l2"]
    got = ops.l2_topk_masked(q, pools, ids, k=k, block_c=block_c)
    with jax.default_matmul_precision("highest"):
        want = ref.l2_topk_masked_ref(q, pools, ids, k)
    out["l2_topk_masked"] = {"shape": list(pools.shape), "k": k,
                             **_same_topk(*got, *want)}
    if "pq" in launches:
        luts, codes, ids, k, block_c = launches["pq"]
        got = ops.pq_adc_masked(luts, codes, ids, k=k, block_c=block_c)
        want = ref.pq_adc_masked_ref(luts, codes, ids, k)
        out["pq_adc_masked"] = {"shape": list(codes.shape), "k": k,
                                **_same_topk(*got, *want)}
    return out


def _memory() -> Dict[str, Optional[int]]:
    stats = jax.devices()[0].memory_stats() or {}
    return {"hbm_bytes_in_use": stats.get("bytes_in_use"),
            "hbm_peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000,
                    help="base vectors")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2
    if ops.default_interpret():
        print("chip_smoke: kernels resolve to interpret mode",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    emit("setup", device=device, compile_cache=enable_compile_cache(),
         search=SEARCH, build=BUILD)

    dep = build(args.n, args.seed)
    emit("build", n=dep.ds.n, d=dep.ds.d, n_queries=len(dep.ds.queries),
         n_parts=int(dep.pag.n_parts), data_wall_s=dep.data_wall_s,
         build_wall_s=dep.build_wall_s, write_wall_s=dep.write_wall_s,
         **_memory())

    for plane in ("none", "pq"):
        res = serve(dep, plane)
        recall = recall_at_k(res.ids, dep.ds.gt_ids, K)
        parity = check_parity(res.launches)
        bt = np.asarray(res.batch_wall_s)
        emit("serve", plane=plane, batches=len(bt), batch_size=BATCH,
             compiles_after_warmup=res.compiles,
             batch_wall_s_p50=float(np.median(bt)),
             batch_wall_s_max=float(bt.max()),
             batch_wall_s_total=float(bt.sum()),
             qps_wall=float(len(dep.ds.queries) / bt.sum()),
             recall_at_10=recall, parity=parity,
             modelled_latency_s_p50=float(np.median(
                 res.modelled_latency_s)),
             **_memory())
        if res.compiles:
            raise RuntimeError(f"{plane}: {res.compiles} compiles after "
                               "warm-up")
        if plane == "none" and recall < RECALL_FLOOR:
            raise RuntimeError(f"float-plane recall@10 {recall:.4f} < "
                               f"{RECALL_FLOOR}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
