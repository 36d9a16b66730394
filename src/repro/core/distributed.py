"""Distributed serving of the PAG index.

* ShardedServing: partitions round-robined over shards; the replicated
  in-memory PG routes queries; queries go through the batch-coalesced
  data plane (core/search.py: cross-query coalesced get_many fetches,
  one Pallas pool scan per batch). Shard failure ->
  the router drops that shard's partitions (bounded recall degradation,
  tests/test_fault_tolerance.py); stragglers tamed by hedged duplicate
  fetches.

* anns_serve_step / anns_build_assign_step: the jax-native pod-scale data
  plane, written with shard_map over the production mesh — these are the
  ops the multi-pod dry-run lowers for the paper's own system (the
  ``anns-*`` cells of ``launch/dryrun.py``). The `data` axis shards the
  residual database; the `model` axis replicates query batches (replica
  parallelism); the top-k merge is an all-gather of k-candidates.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Set

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distances import cdist2
from repro.core.pag import PAG
from repro.core.search import SearchConfig, search_pag
from repro.storage.simulator import ComputeModel, ObjectStore


# --------------------------------------------------------------------------
# router-level sharded serving (simulation-backed, exact results)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedServing:
    pag: PAG
    store: ObjectStore
    n_shards: int
    dim: int
    prefix: str = "part"
    replicas: int = 1           # replica layout written by write_partitions
    dead_shards: Set[int] = dataclasses.field(default_factory=set)
    resilient: Optional[object] = None   # long-lived ResilientStore

    def kill_shard(self, shard: int):
        self.dead_shards.add(shard)
        self.store.kill_prefix(f"{self.prefix}/{shard}/")

    def revive(self):
        self.dead_shards.clear()
        self.store.revive_all()

    def enable_resilience(self, policy) -> "ShardedServing":
        """Install a long-lived retry/failover/breaker plane: breaker
        state persists across searches, so a dead shard stops eating
        retry budget after a few queries instead of per batch."""
        from repro.storage.resilience import ResilientStore
        self.resilient = ResilientStore(self.store, policy)
        return self

    def rebalance(self, new_n_shards: int):
        """Elastic scaling: re-map partitions across a new shard count by
        rewriting objects under the new prefix layout (on a real cluster
        this is a background copy between storage nodes; results are
        identical throughout because the router owns the mapping)."""
        moved = 0
        for pid in range(self.pag.n_parts):
            old_key = f"{self.prefix}/{pid % self.n_shards}/{pid}"
            new_key = f"{self.prefix}/{pid % new_n_shards}/{pid}"
            if old_key == new_key:
                continue
            obj = self.store._data.get(old_key)
            if obj is None:
                continue
            self.store.put(new_key, obj)
            del self.store._data[old_key]
            moved += 1
        self.n_shards = new_n_shards
        return moved

    def search(self, queries: np.ndarray, cfg: SearchConfig,
               compute: Optional[ComputeModel] = None, **kw):
        """``**kw`` passes the micro-batch pipeline arguments through to
        ``search_pag`` (``prefetched`` / ``prefetch_probes`` /
        ``trace_t0_s`` — see ``serving.engine.AnnsFrontend``)."""
        if self.replicas > 1 and cfg.replicas == 1:
            cfg = dataclasses.replace(cfg, replicas=self.replicas)
        if self.resilient is not None and cfg.resilience is None:
            cfg = dataclasses.replace(cfg, resilience=self.resilient)
        return search_pag(self.pag, self.dim, queries, self.store, cfg,
                          compute=compute, prefix=self.prefix,
                          n_shards=self.n_shards,
                          dead_shard_fallback=True, **kw)


# --------------------------------------------------------------------------
# pod-scale data plane (shard_map; lowered by the dry-run)
# --------------------------------------------------------------------------

def _all_axes(mesh: Mesh):
    return tuple(mesh.axis_names)


def make_anns_serve_step(mesh: Mesh, k: int = 100):
    """DSANN's serving data plane at pod scale: every device owns a block
    of residual partitions (the whole database sharded over ALL mesh axes
    — the "distributed storage" tier is the pod's aggregate HBM); the
    replicated in-memory PG has already produced, per query, the probed
    partitions' local row ids on each owner rank. The step gathers those
    rows (the async fetch), full-scans them (fused distance+top-k — the
    Pallas l2_topk target), and merges top-k hierarchically across the
    mesh (the I/O+merge pattern of Alg 5).

    Inputs:  queries [Q, d] (replicated),
             db_block [N_loc, d] per rank,
             rows [Q, P_loc * cap] int32 local row ids (per rank).
    Returns: (ids [Q, k] global row ids, d2 [Q, k]).
    """
    axes = _all_axes(mesh)

    def step(queries, db, rows):
        def body(q, db_blk, rows_blk):
            n_local = db_blk.shape[0]
            fetched = db_blk[rows_blk]                    # [Q, Pc, d]
            diff = fetched - q[:, None, :]
            d2 = jnp.einsum("qpd,qpd->qp", diff, diff)
            neg, idx = jax.lax.top_k(-d2, min(k, d2.shape[1]))
            local_ids = jnp.take_along_axis(rows_blk, idx, axis=1)
            r = jax.lax.axis_index(axes[0])
            for a in axes[1:]:
                r = r * jax.lax.axis_size(a) + jax.lax.axis_index(a)
            gids = local_ids + r * n_local
            for a in axes:                                # hierarchical merge
                neg = jax.lax.all_gather(neg, a, axis=1, tiled=True)
                gids = jax.lax.all_gather(gids, a, axis=1, tiled=True)
                neg, pos = jax.lax.top_k(neg, min(k, neg.shape[1]))
                gids = jnp.take_along_axis(gids, pos, axis=1)
            return gids, -neg

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(*([None] * 2)), P(axes, None), P(None, None)),
            out_specs=(P(None, None), P(None, None)),
            check_vma=False,
        )(queries, db, rows)

    return step


def make_anns_assign_step(mesh: Mesh, k: int = 8, row_chunk: int = 4096,
                          col_chunk: int = 65536):
    """DRS/CIC assignment data plane: residual blocks sharded over the
    data axes find their k nearest aggregation points; the aggregation set
    (p*n, too big to replicate at billion scale) is sharded over the model
    axis, with a hierarchical top-k merge — the dominant compute of index
    construction (Alg 3 line 16), distributed.

    The distance matrix is never materialized: rows and agg columns are
    double-chunked with a running top-k (the l2_topk kernel pattern at
    pod scale) — the naive [N_loc, m_loc] product was a 2.27 TB/device
    temp at BigANN scale."""
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def step(residuals, agg):
        def body(r_blk, agg_blk):
            m_local = agg_blk.shape[0]
            n_local = r_blk.shape[0]
            rc = min(row_chunk, n_local)
            cc = min(col_chunk, m_local)
            assert n_local % rc == 0 and m_local % cc == 0
            agg_c = agg_blk.reshape(m_local // cc, cc, agg_blk.shape[1])

            def row_block(r_sub):
                def col_scan(carry, inp):
                    best_neg, best_ids = carry
                    j, a_sub = inp
                    d2 = cdist2(r_sub, a_sub)             # [rc, cc]
                    neg, idx = jax.lax.top_k(-d2, k)
                    ids = idx + j * cc
                    neg = jnp.concatenate([best_neg, neg], axis=1)
                    ids = jnp.concatenate([best_ids, ids], axis=1)
                    neg, pos = jax.lax.top_k(neg, k)
                    ids = jnp.take_along_axis(ids, pos, axis=1)
                    return (neg, ids), None

                init = (jnp.full((rc, k), -3.4e38, jnp.float32),
                        jnp.full((rc, k), -1, jnp.int32))
                (neg, ids), _ = jax.lax.scan(
                    col_scan, init,
                    (jnp.arange(m_local // cc), agg_c))
                return neg, ids

            r_c = r_blk.reshape(n_local // rc, rc, r_blk.shape[1])
            neg, idx = jax.lax.map(row_block, r_c)
            neg = neg.reshape(n_local, k)
            idx = idx.reshape(n_local, k)
            gids = idx + jax.lax.axis_index("model") * m_local
            neg = jax.lax.all_gather(neg, "model", axis=1, tiled=True)
            gids = jax.lax.all_gather(gids, "model", axis=1, tiled=True)
            neg, pos = jax.lax.top_k(neg, k)
            gids = jnp.take_along_axis(gids, pos, axis=1)
            return gids, -neg

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(dp_spec, None), P("model", None)),
            out_specs=(P(dp_spec, None), P(dp_spec, None)),
            check_vma=False,
        )(residuals, agg)

    return step
