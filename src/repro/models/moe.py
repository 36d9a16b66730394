"""Top-k MoE with sort-based (MegaBlocks-style) token dispatch.

Two execution paths:

* local (tests / single device): tokens argsorted by expert into [E, C, D]
  buffers, batched expert einsum, weighted combine. No dispatch tensor —
  O(Tk log Tk + ECD) instead of GShard's O(T·E·C).
* sharded (production mesh, via the ambient mesh context): explicit
  shard_map expert parallelism. Tokens are data-sharded and *replicated*
  over the model axis; each model rank dispatches only to its E/mp local
  experts (purely local sort), FSDP weight shards are all-gathered over the
  data axes, and per-rank partial outputs are psum'd over the model axis —
  one [T_loc, D] all-reduce per MoE layer, the Megatron-TP communication
  pattern. This keeps GSPMD away from global sort/scatter partitioning
  (which would otherwise replicate terabyte-scale buffers).

Capacity overflow drops follow GShard semantics in both paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init, softmax_fp32


def moe_param_shapes(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    shapes = {
        "router": (d, e),
        "w_gate": (e, d, f),
        "w_up": (e, d, f),
        "w_down": (e, f, d),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        shapes.update({
            "shared_gate": (d, fs), "shared_up": (d, fs),
            "shared_down": (fs, d),
        })
    return shapes


def init_moe(key, cfg, dtype):
    shapes = moe_param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        in_axis = 1 if name.startswith("w_") else 0
        out[name] = dense_init(k, shape, in_axis=in_axis, dtype=dtype)
    return out


def _dispatch_compute(xf, gate_w, gate_e, w_gate, w_up, w_down, *,
                      n_experts, top_k, cap, expert_offset=0):
    """Sort-based dispatch + expert einsum + combine over [T, D] tokens.

    Experts [expert_offset, expert_offset + E_local) are computed; tokens
    routed elsewhere contribute zero (callers psum partials across ranks).
    """
    t, d = xf.shape
    e_local = w_gate.shape[0]
    n_assign = t * top_k
    flat_e = gate_e.reshape(n_assign) - expert_offset           # [T*k]
    flat_tok = jnp.repeat(jnp.arange(t), top_k)
    flat_w = gate_w.reshape(n_assign)
    local = (flat_e >= 0) & (flat_e < e_local)
    flat_e = jnp.where(local, flat_e, e_local)                  # park at E

    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    w_sorted = flat_w[order]
    local_sorted = local[order]

    counts = jnp.bincount(flat_e, length=e_local + 1)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(n_assign) - offsets[e_sorted]

    keep = (pos_in_expert < cap) & local_sorted
    slot = jnp.where(keep, e_sorted * cap + pos_in_expert, 0)

    buf = jnp.zeros((e_local * cap, d), xf.dtype)
    gathered = jnp.where(keep[:, None], xf[tok_sorted], 0)
    buf = buf.at[slot].add(gathered)
    buf = buf.reshape(e_local, cap, d)

    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xf.dtype) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)
    out_buf = out_buf.reshape(e_local * cap, d)

    contrib = out_buf[slot] * w_sorted[:, None].astype(xf.dtype)
    contrib = jnp.where(keep[:, None], contrib, 0)
    return jnp.zeros((t, d), xf.dtype).at[tok_sorted].add(contrib)


def _route(xf, router, top_k):
    logits = (xf @ router).astype(jnp.float32)                  # [T, E]
    probs = softmax_fp32(logits)
    gate_w, gate_e = jax.lax.top_k(probs, top_k)                # [T, k]
    gate_w = gate_w / jnp.maximum(
        jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)
    return gate_w, gate_e


def _shared_experts(params, xf):
    sg = xf @ params["shared_gate"]
    su = xf @ params["shared_up"]
    sh = jax.nn.silu(sg.astype(jnp.float32)).astype(xf.dtype) * su
    return sh @ params["shared_down"]


def _moe_local(params, x, cfg):
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    gate_w, gate_e = _route(xf, params["router"], cfg.moe_top_k)
    cap = max(int(cfg.capacity_factor * t * cfg.moe_top_k / cfg.n_experts), 1)
    out = _dispatch_compute(xf, gate_w, gate_e, params["w_gate"],
                            params["w_up"], params["w_down"],
                            n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                            cap=cap)
    if cfg.n_shared_experts:
        out = out + _shared_experts(params, xf)
    return out.reshape(b, s, d)


def _moe_sharded(params, x, cfg, mesh, dist):
    """shard_map expert parallelism (see module docstring)."""
    b, s, d = x.shape
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    mp = mesh.shape.get("model", 1)
    e_local = cfg.n_experts // mp
    t_local = (b * s) // dp if (b * s) % dp == 0 else b * s
    batch_shardable = b % dp == 0
    cap = max(int(cfg.capacity_factor * t_local * cfg.moe_top_k
                  / cfg.n_experts), 1)

    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    x_spec = P(dp_spec, None, None) if batch_shardable else P(None, None, None)
    w_spec = P("model", dp_spec, None)       # FSDP on D, EP on experts
    w_down_spec = P("model", None, dp_spec)

    def body(x_blk, router, wg, wu, wd):
        bb, ss, dd = x_blk.shape
        xf = x_blk.reshape(bb * ss, dd)
        # FSDP all-gather of this rank's expert weights over the data axes
        # (minor axis first so block order reconstructs the original dim)
        for ax in reversed(dp_axes):
            wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, ax, axis=2, tiled=True)
        gate_w, gate_e = _route(xf, router, cfg.moe_top_k)
        my_rank = jax.lax.axis_index("model")
        out = _dispatch_compute(
            xf, gate_w, gate_e, wg, wu, wd, n_experts=cfg.n_experts,
            top_k=cfg.moe_top_k, cap=cap, expert_offset=my_rank * e_local)
        out = jax.lax.psum(out, "model")
        return out.reshape(bb, ss, dd)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_down_spec),
        out_specs=x_spec,
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])

    if cfg.n_shared_experts:
        out = out + _shared_experts(params, x.reshape(b * s, d)).reshape(
            b, s, d)
    return out


def moe_forward(params, x, cfg):
    """x [B, S, D] -> [B, S, D]."""
    from repro.distributed.context import get_mesh

    mesh, dist = get_mesh()
    if (mesh is not None and mesh.shape.get("model", 1) > 1
            and cfg.n_experts % mesh.shape["model"] == 0):
        return _moe_sharded(params, x, cfg, mesh, dist)
    return _moe_local(params, x, cfg)


def moe_aux_loss(params, x, cfg):
    """Switch-style load-balance auxiliary loss (returned by train_step)."""
    b, s, d = x.shape
    t = b * s
    logits = (x.reshape(t, d) @ params["router"]).astype(jnp.float32)
    probs = softmax_fp32(logits)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
