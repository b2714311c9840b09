"""Mixture-of-Experts layer: top-k routing with capacity-based dispatch.

Dispatch is scatter/gather based (no (tokens x experts x capacity) one-hot
einsum): tokens are assigned a position inside their expert's capacity
buffer via a running count; overflow tokens are dropped (their residual
passes through), exactly like Switch/GShard capacity routing.

**Locality-grouped dispatch (§Perf hillclimb)**: under SPMD, a single
global scatter forces XLA to materialize and all-reduce the whole
(E, C, D) buffer across the data axis (TB-scale per step for dbrx).  We
instead split tokens into ``groups`` aligned with the data shards; each
group scatters into its own (E, C/g, D) slab via a vmapped local scatter,
so the only cross-device movement is the (group <-> expert) resharding in
front of the expert einsum — the canonical MoE all-to-all.  Experts shard
over the model axis (EP).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_dense
from repro.parallel import ctx


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype,
             n_local: Optional[int] = None):
    """A router over ``n_experts`` and the weights of all of them, or,
    given ``n_local``, of an expert-parallel share of that many: a
    share's matrices are scaled by their own fan-in (``d_model``,
    ``d_ff``), so that an expert is drawn alike whatever the share's
    size (the whole layer's default scale is ``1/sqrt(n_experts)``)."""
    ks = jax.random.split(key, 4)
    e, up, down = ((n_experts, None, None) if n_local is None
                   else (n_local, d_model ** -0.5, d_ff ** -0.5))
    return {
        "router": init_dense(ks[0], (d_model, n_experts), dtype),
        "w_gate": init_dense(ks[1], (e, d_model, d_ff), dtype, scale=up),
        "w_up": init_dense(ks[2], (e, d_model, d_ff), dtype, scale=up),
        "w_down": init_dense(ks[3], (e, d_ff, d_model), dtype, scale=down),
    }


def _dispatch_groups(t: int) -> int:
    """Token groups = product of the active batch mesh axes (1 off-mesh)."""
    mesh = ctx.current_mesh()
    if mesh is None:
        return 1
    g = 1
    for ax in ctx.batch_axes():
        g *= mesh.shape.get(ax, 1)
    while g > 1 and t % g != 0:
        g //= 2
    return max(g, 1)


def capacity_slots(flat_e: jnp.ndarray, n_experts: int,
                   capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The capacity rule: within each group (the leading axes of
    ``flat_e``, (..., T*K), the experts of each (token, k) assignment,
    token-major), an assignment takes the next slot of its expert; slots
    past ``capacity`` are dropped.  Returns (slot, kept), (..., T*K)."""
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=-2) - 1,
                              flat_e[..., None], -1)[..., 0]
    return pos, pos < capacity


def moe_layer(params: Dict, x: jnp.ndarray, top_k: int,
              capacity_factor: float = 1.25,
              aux_weight: float = 0.01,
              groups: Optional[int] = None) -> Tuple[jnp.ndarray,
                                                     jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss)."""
    b, s, d = x.shape
    t = b * s
    g = groups if groups is not None else _dispatch_groups(t)
    tg = t // g
    n_experts = params["router"].shape[1]
    capacity = int(max(1, round(tg * top_k / n_experts * capacity_factor)))

    xg = x.reshape(g, tg, d)
    xg = ctx.constrain(xg, "batch", None, None)
    logits = (xg.astype(jnp.float32)
              @ params["router"].astype(jnp.float32))     # (g, tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)            # (g, tg, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(g, tg * top_k)                 # (g, T_g*K)
    flat_pos, keep = capacity_slots(flat_e, n_experts, capacity)
    safe_pos = jnp.where(keep, flat_pos, 0)
    token_idx = jnp.repeat(jnp.arange(tg), top_k)         # shared per group

    def scatter_group(xg_, fe, sp, keep_):
        contrib = jnp.where(keep_[:, None], xg_[token_idx], 0)
        buf = jnp.zeros((n_experts, capacity, d), x.dtype)
        return buf.at[fe, sp].add(contrib)

    dispatched = jax.vmap(scatter_group)(xg, flat_e, safe_pos, keep)
    # E-major layout: (E@model, g@batch, C, D).  The expert einsums then
    # contract entirely locally (weights are E@model too); the only
    # cross-device movement is inside the scatter/gather — the MoE
    # all-to-all — instead of a whole-buffer reshard around the einsum.
    dispatched = jnp.swapaxes(dispatched, 0, 1)           # (E, g, C, D)
    dispatched = ctx.constrain(dispatched, "model", "batch", None, None)

    # Grouped expert FFN (SwiGLU): (E, g, C, D) x (E, D, F)
    gate = jax.nn.silu(jnp.einsum("egcd,edf->egcf", dispatched,
                                  params["w_gate"]))
    up = jnp.einsum("egcd,edf->egcf", dispatched, params["w_up"])
    expert_out = jnp.einsum("egcf,efd->egcd", gate * up, params["w_down"])
    expert_out = ctx.constrain(expert_out, "model", "batch", None, None)
    expert_out = jnp.swapaxes(expert_out, 0, 1)           # (g, E, C, D)

    def gather_group(eo, fe, sp, keep_, tp):
        gathered = eo[fe, sp]                             # (T_g*K, D)
        w = (tp.reshape(-1) * keep_).astype(x.dtype)
        return jax.ops.segment_sum(gathered * w[:, None], token_idx,
                                   num_segments=tg)

    combined = jax.vmap(gather_group)(expert_out, flat_e, safe_pos, keep,
                                      top_p)
    combined = ctx.constrain(combined, "batch", None, None)

    # Load-balancing auxiliary loss (Switch-style), global over all groups.
    me = probs.reshape(t, n_experts).mean(0)
    ce = jax.nn.one_hot(top_e.reshape(t, top_k)[:, 0], n_experts).mean(0)
    aux = aux_weight * n_experts * jnp.sum(me * ce)
    return combined.reshape(b, s, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Expert-parallel share with top-k softmax gating (granitemoehybrid)
# ---------------------------------------------------------------------------
def topk_softmax_gate(logits: jnp.ndarray, top_k: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Granite's gate: the ``top_k`` largest router logits of each token
    and a softmax over those alone.  Returns (gates, experts), (..., K)."""
    top_l, top_e = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top_l, axis=-1), top_e




def local_moe_layer(params: Dict, x: jnp.ndarray, top_k: int,
                    expert_offset: int = 0,
                    capacity_factor: float = 1.25) -> jnp.ndarray:
    """The part of a top-k MoE layer that one expert-parallel share gives.

    ``params["router"]`` is (D, E) over every expert; the expert weights
    hold this share's ``n_local`` experts, ``expert_offset`` onward.  The
    gate is :func:`topk_softmax_gate`, the capacity that of the whole
    layer (``round(T * K / E * capacity_factor)`` slots an expert, filled
    in token order).  Only assignments to the held experts are computed:
    gathered into (n_local, C, D) by a slot table, run through the SwiGLU
    experts and scattered back weighted by their gates.  The shares'
    outputs sum to the whole layer's.  x: (B, S, D) -> (B, S, D)."""
    b, s, d = x.shape
    t = b * s
    n_experts = params["router"].shape[1]
    n_local = params["w_gate"].shape[0]
    capacity = int(max(1, round(t * top_k / n_experts * capacity_factor)))
    xt = x.reshape(t, d)
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    gates, top_e = topk_softmax_gate(logits, top_k)         # (T, K)
    pos, kept = capacity_slots(top_e.reshape(-1), n_experts, capacity)
    local = top_e.reshape(-1) - expert_offset
    kept = kept & (local >= 0) & (local < n_local)
    n_slots = n_local * capacity
    slot = jnp.where(kept, local * capacity + pos, n_slots)  # drop -> spare
    token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    # slot -> token (t: the zero row) and its gate; the spare slot is cut
    slot_token = jnp.full((n_slots + 1,), t, jnp.int32).at[slot].set(
        token)[:n_slots]
    slot_gate = jnp.zeros((n_slots + 1,), jnp.float32).at[slot].set(
        gates.reshape(-1))[:n_slots]
    xz = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])
    dispatched = xz[slot_token].reshape(n_local, capacity, d)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", dispatched,
                                  params["w_gate"]))
    up = jnp.einsum("ecd,edf->ecf", dispatched, params["w_up"])
    out = jnp.einsum("ecf,efd->ecd", gate * up, params["w_down"])
    weighted = out.reshape(n_slots, d) * slot_gate[:, None].astype(x.dtype)
    combined = jnp.zeros((t + 1, d), x.dtype).at[slot_token].add(weighted)
    return combined[:t].reshape(b, s, d)
