"""Mixture-of-Experts FFN: the paper's blocked-sparsity regime in production.

Token->expert assignment makes the expert FFN a *block-diagonal* SpMM
(DESIGN.md Section 6): after bucketing tokens by expert, each expert's weight
matrix multiplies a dense block of tokens — the best case of the paper's
blocked model (z = t, MXU utilization 1).  On real TPUs the per-expert
matmuls run through the grouped_matmul Pallas kernel (repro.kernels); the
pjit path below expresses the same computation with scatter/gather dispatch
so that *no fake FLOPs* appear in the compiled HLO (a one-hot dispatch einsum
would add O(T*E*C*d) bogus compute and poison the roofline analysis).

Two paths:
  moe_ffn_dense    oracle: every expert computes every token, combined by
                   router weights (tiny configs / tests only).
  moe_ffn          production: shard_map over (data..., model) — tokens are
                   replicated across the model axis (they arrive that way in
                   Megatron-style TP), each model shard owns E/TP experts,
                   selects + buckets its tokens locally (capacity C), runs
                   the expert FFN, and psums partial outputs across "model".
                   Expert weights are stored sharded (E over model, d_model
                   over data) and all-gathered over "data" per layer (FSDP).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L


def init_moe(key, d: int, d_ff: int, num_experts: int) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": L.init_dense(k1, d, num_experts),
        "w_gate": L.he_init(k2, (num_experts, d, d_ff), fan_in=d),
        "w_up": L.he_init(k3, (num_experts, d, d_ff), fan_in=d),
        "w_down": L.he_init(k4, (num_experts, d_ff, d), fan_in=d_ff),
    }


def _router(router_params: Dict, x: jnp.ndarray, k: int):
    """Top-k routing. x: [T, d] -> (weights [T,k] f32, ids [T,k] i32)."""
    logits = (x.astype(jnp.float32)
              @ router_params["kernel"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    return weights, ids.astype(jnp.int32)


def _expert_ffn(w_gate, w_up, w_down, buf: jnp.ndarray) -> jnp.ndarray:
    """buf: [E_loc, C, d] -> [E_loc, C, d] (SwiGLU), batched over experts.

    This is the block-diagonal BCSR SpMM; on TPU it maps to
    kernels.grouped_matmul.grouped_matmul_pallas with group blocks of C
    rows.
    """
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _capacity(t_local: int, k: int, num_experts: int,
              capacity_factor: float) -> int:
    c = int((t_local * k * capacity_factor) / num_experts) + 1
    c = max(c, min(8, t_local * k))
    return min(c, t_local * k)


def _bucket_local(x, weights, ids, e0: int, e_loc: int, capacity: int):
    """Bucket tokens routed to experts [e0, e0+e_loc) into a capacity buffer.

    x: [T, d]; weights/ids: [T, k].  Returns (buffer [E_loc, C, d],
    combine spec (e_idx, c_idx, keep*w) each [T, k]).
    Pure gather/scatter — no arithmetic beyond the cumsum bookkeeping.
    """
    T, d = x.shape
    k = ids.shape[1]
    local = (ids >= e0) & (ids < e0 + e_loc)
    e_local = jnp.clip(ids - e0, 0, e_loc - 1)

    # Position of each (token, slot) within its expert, counted over the
    # flattened slot-major order (GShard-style sequential ranks).
    pos = jnp.zeros((T, k), jnp.int32)
    counts = jnp.zeros((e_loc,), jnp.int32)
    for r in range(k):
        onehot = (jnp.arange(e_loc)[None, :] == e_local[:, r][:, None])
        onehot = onehot & local[:, r][:, None]          # [T, E_loc] bool
        within = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        pos = pos.at[:, r].set(
            jnp.take_along_axis(within + counts[None, :],
                                e_local[:, r][:, None], axis=1)[:, 0])
        counts = counts + jnp.sum(onehot.astype(jnp.int32), axis=0)

    keep = local & (pos < capacity)
    buf = jnp.zeros((e_loc, capacity, d), x.dtype)
    flat_e = jnp.where(keep, e_local, 0).reshape(-1)
    flat_c = jnp.where(keep, pos, 0).reshape(-1)
    updates = jnp.repeat(x[:, None, :], k, axis=1).reshape(-1, d)
    updates = updates * keep.reshape(-1, 1).astype(x.dtype)
    buf = buf.at[flat_e, flat_c].add(updates)
    return buf, (e_local, jnp.clip(pos, 0, capacity - 1),
                 weights * keep.astype(weights.dtype))


def _combine_local(out_buf, combine, T: int) -> jnp.ndarray:
    e_idx, c_idx, w = combine                      # each [T, k]
    gathered = out_buf[e_idx, c_idx]               # [T, k, d]
    return jnp.sum(gathered * w[..., None].astype(gathered.dtype), axis=1)


def _moe_local(x, router, w_gate, w_up, w_down, *, k: int, num_experts: int,
               e0: int, capacity_factor: float) -> jnp.ndarray:
    """Per-device MoE over this shard's experts; x: [T, d] local tokens."""
    T, d = x.shape
    e_loc = w_gate.shape[0]
    weights, ids = _router(router, x, k)
    cap = _capacity(T, k, num_experts, capacity_factor)
    buf, combine = _bucket_local(x, weights, ids, e0, e_loc, cap)
    out_buf = _expert_ffn(w_gate.astype(x.dtype), w_up.astype(x.dtype),
                          w_down.astype(x.dtype), buf)
    return _combine_local(out_buf, combine, T)


def moe_ffn(params: Dict, x: jnp.ndarray, *, k: int, num_experts: int,
            capacity_factor: float = 1.25, ctx=None) -> jnp.ndarray:
    """MoE FFN. x: [B, S, d].  Uses shard_map when ctx carries a mesh."""
    B, S, d = x.shape
    mesh = getattr(ctx, "mesh", None)
    if mesh is None or "model" not in mesh.axis_names:
        flat = x.reshape(B * S, d)
        out = _moe_local(flat, params["router"], params["w_gate"],
                         params["w_up"], params["w_down"], k=k,
                         num_experts=num_experts, e0=0,
                         capacity_factor=capacity_factor)
        return out.reshape(B, S, d)

    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    tp = mesh.shape["model"]
    assert num_experts % tp == 0, (num_experts, tp)
    # Weights are FSDP-sharded over "data" only (replicated across "pod" —
    # hybrid ZeRO, DESIGN.md Section 4); gathered per layer inside the block.
    w_ax = "data" if ("data" in mesh.axis_names
                      and mesh.shape["data"] > 1) else None

    # Sequence-scatter the combined output when the local seq divides TP:
    # the layer boundary is seq-sharded anyway (Megatron SP), so psum +
    # re-shard would move TP x more bytes than psum_scatter.
    seq_local = S
    scatter_ok = seq_local % tp == 0 and seq_local > 1

    def shard_fn(x_loc, router, w_gate, w_up, w_down):
        if w_ax is not None:
            # FSDP all-gather in the compute dtype: gathering fp32 masters
            # doubles the dominant collective of the MoE cells
            # (EXPERIMENTS.md Section Perf, hypothesis P5).
            w_gate = jax.lax.all_gather(
                w_gate.astype(x_loc.dtype), w_ax, axis=1, tiled=True)
            w_up = jax.lax.all_gather(
                w_up.astype(x_loc.dtype), w_ax, axis=1, tiled=True)
            w_down = jax.lax.all_gather(
                w_down.astype(x_loc.dtype), w_ax, axis=2, tiled=True)
        e_loc = w_gate.shape[0]
        e0 = jax.lax.axis_index("model") * e_loc
        Bl, Sl, _ = x_loc.shape
        out = _moe_local(x_loc.reshape(Bl * Sl, d), router, w_gate, w_up,
                         w_down, k=k, num_experts=num_experts, e0=e0,
                         capacity_factor=capacity_factor)
        out = out.reshape(Bl, Sl, d)
        if scatter_ok:
            # Row-parallel partial sums -> sequence shards (SP boundary).
            return jax.lax.psum_scatter(out, "model", scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(out, "model")

    out_spec = P(batch_axes, "model", None) if scatter_ok \
        else P(batch_axes, None, None)
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(batch_axes, None, None),               # x
                  P(),                                     # router (replic.)
                  P("model", w_ax, None),                  # w_gate [E,d,ff]
                  P("model", w_ax, None),                  # w_up
                  P("model", None, w_ax)),                 # w_down [E,ff,d]
        out_specs=out_spec,
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])


def moe_ffn_dense(params: Dict, x: jnp.ndarray, *, k: int,
                  num_experts: int) -> jnp.ndarray:
    """Oracle: compute all experts for all tokens (tests / tiny configs)."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    weights, ids = _router(params["router"], flat, k)
    w_gate = params["w_gate"].astype(x.dtype)
    w_up = params["w_up"].astype(x.dtype)
    w_down = params["w_down"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", flat, w_gate)) * \
        jnp.einsum("td,edf->tef", flat, w_up)
    all_out = jnp.einsum("tef,efd->ted", h, w_down)       # [T, E, d]
    gate = jnp.zeros((flat.shape[0], num_experts), jnp.float32)
    gate = gate.at[jnp.arange(flat.shape[0])[:, None], ids].add(weights)
    out = jnp.einsum("ted,te->td", all_out.astype(jnp.float32), gate)
    return out.reshape(B, S, d).astype(x.dtype)


def sparse_component_spec(cfg, shape, t_tokens: int) -> Dict:
    """Paper-model metadata for the analyzer: MoE as blocked sparsity.

    A = token x token-slot block-diagonal matrix: one t x t dense block per
    capacity bucket; d = d_model (the dense operand width).
    """
    return {
        "name": f"moe_dispatch/{cfg.name}",
        "regime": "blocked_tpu",
        "n": t_tokens * cfg.num_experts_per_token,
        "nnz": t_tokens * cfg.num_experts_per_token * 128,
        "t": 128,
        "num_blocks": max(
            1, t_tokens * cfg.num_experts_per_token // 128),
        "d": cfg.d_model,
        "sizeof_val": 2,
    }
