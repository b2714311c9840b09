"""Unified decoder model covering all assigned architecture families.

One functional model with ``init_params`` / ``forward`` / ``loss_fn`` /
``prefill`` / ``decode_step``, driven entirely by :class:`ModelConfig`:

  * dense / moe / vlm / audio : pre-norm attention + (SwiGLU | MoE) blocks,
    stacked with ``lax.scan`` (per-layer window sizes ride along as scan xs,
    which is how gemma3's 5:1 local:global pattern compiles to ONE block).
  * ssm    : Mamba2 (SSD) blocks.
  * hybrid : zamba2-style supercells — ``attn_every`` Mamba2 layers followed
    by one application of a *weight-shared* attention+MLP block.
  * granitemoehybrid : granite 4.0-h — a mixer per layer (Mamba2, or NoPE
    GQA), each followed by this chip's share of the routed experts and a
    shared expert, with the published multipliers; one period of
    ``layer_types`` is unrolled in a ``lax.scan`` body over the periods.
    Training only (no prefill or decode path).

VLM / audio frontends are stubs per the assignment: ``prefix_embeds``
(precomputed patch/frame embeddings) are linearly projected and prepended.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (cross_entropy, init_dense, rms_norm,
                                 apply_rope, swiglu)
from repro.parallel import ctx

#: batch axes for activation sharding hints (no-ops without a mesh)
_BATCH = ("pod", "data")


def _shard_act(x):
    """Keep (B, S, D) activations batch- (and, under the sequence-parallel
    profile, sequence-) sharded through scans."""
    return ctx.constrain(x, "batch", "seq", None)


def _remat(fn, cfg):
    """Wrap a scan body with the configured activation-checkpoint policy."""
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------
def _init_attn_block(key, cfg: ModelConfig, dtype) -> Dict:
    d, h, kv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim)
    ks = jax.random.split(key, 6)
    p = {
        "ln1": jnp.ones((d,), dtype),
        "wq": init_dense(ks[0], (d, h * hd), dtype),
        "wk": init_dense(ks[1], (d, kv * hd), dtype),
        "wv": init_dense(ks[2], (d, kv * hd), dtype),
        "wo": init_dense(ks[3], (h * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _init_mlp_block(key, cfg: ModelConfig, dtype) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    p = {"ln2": jnp.ones((d,), dtype)}
    if cfg.n_experts:
        p["moe"] = moe_mod.init_moe(key, d, f, cfg.n_experts, dtype)
    else:
        ks = jax.random.split(key, 3)
        p["w_gate"] = init_dense(ks[0], (d, f), dtype)
        p["w_up"] = init_dense(ks[1], (d, f), dtype)
        p["w_down"] = init_dense(ks[2], (f, d), dtype)
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _init_granite_layer(key, cfg: ModelConfig, kind: str, dtype) -> Dict:
    d, sf = cfg.d_model, cfg.shared_ff
    ks = jax.random.split(key, 5)
    blk = {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
           "moe": moe_mod.init_moe(ks[1], d, cfg.d_ff, cfg.n_experts, dtype,
                                   n_local=cfg.experts_held),
           "shared": {"w_gate": init_dense(ks[2], (d, sf), dtype),
                      "w_up": init_dense(ks[3], (d, sf), dtype),
                      "w_down": init_dense(ks[4], (sf, d), dtype)}}
    if kind == "mamba":
        blk["mamba"] = ssm_mod.init_mamba_block(ks[0], cfg, dtype)
    else:
        attn = _init_attn_block(ks[0], cfg, dtype)
        blk.update({k: attn[k] for k in ("wq", "wk", "wv", "wo")})
    return blk


def init_params(cfg: ModelConfig, key) -> Dict:
    dtype = _dtype(cfg)
    keys = jax.random.split(key, cfg.n_layers + 8)
    params: Dict[str, Any] = {
        "embed": init_dense(keys[-1], (cfg.vocab_size, cfg.d_model), dtype,
                            scale=0.02),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[-2],
                                       (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.frontend:
        params["frontend_proj"] = init_dense(
            keys[-3], (cfg.frontend_dim, cfg.d_model), dtype)

    if cfg.family == "ssm":
        blocks = [dict(ln=jnp.ones((cfg.d_model,), dtype),
                       **{"mamba": ssm_mod.init_mamba_block(keys[i], cfg,
                                                            dtype)})
                  for i in range(cfg.n_layers)]
        params["layers"] = _stack(blocks)
    elif cfg.family == "granitemoehybrid":
        # params["layers"][j]: the j-th layer of every period, stacked
        period = cfg.layer_period
        blocks = [_init_granite_layer(keys[i], cfg, cfg.layer_types[i],
                                      dtype) for i in range(cfg.n_layers)]
        params["layers"] = [_stack(blocks[j::period])
                            for j in range(period)]
    elif cfg.family == "hybrid":
        n_groups = cfg.n_layers // cfg.attn_every
        blocks = [dict(ln=jnp.ones((cfg.d_model,), dtype),
                       **{"mamba": ssm_mod.init_mamba_block(keys[i], cfg,
                                                            dtype)})
                  for i in range(cfg.n_layers)]
        grouped = [_stack(blocks[g * cfg.attn_every:(g + 1) * cfg.attn_every])
                   for g in range(n_groups)]
        params["layers"] = _stack(grouped)
        shared = _init_attn_block(keys[-4], cfg, dtype)
        shared.update(_init_mlp_block(keys[-5], cfg, dtype))
        params["shared_attn"] = shared
    else:
        blocks = []
        for i in range(cfg.n_layers):
            blk = _init_attn_block(keys[i], cfg, dtype)
            blk.update(_init_mlp_block(
                jax.random.fold_in(keys[i], 1), cfg, dtype))
            blocks.append(blk)
        params["layers"] = _stack(blocks)
    return params


def layer_windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention windows: 0 = full attention."""
    if cfg.sliding_window and cfg.global_every:
        w = np.full((cfg.n_layers,), cfg.sliding_window, np.int32)
        w[cfg.global_every - 1::cfg.global_every] = 0  # every Nth is global
        return w
    if cfg.sliding_window:
        return np.full((cfg.n_layers,), cfg.sliding_window, np.int32)
    return np.zeros((cfg.n_layers,), np.int32)


# ---------------------------------------------------------------------------
# Blocks (training / prefill form)
# ---------------------------------------------------------------------------
def _attention(blk: Dict, x: jnp.ndarray, cfg: ModelConfig, window,
               positions: jnp.ndarray) -> jnp.ndarray:
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    hn = rms_norm(x, blk["ln1"], cfg.norm_eps)
    q = (hn @ blk["wq"]).reshape(b, s, h, hd)
    k = (hn @ blk["wk"]).reshape(b, s, kv, hd)
    v = (hn @ blk["wv"]).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = rms_norm(k, blk["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.use_flash and s > cfg.attn_chunk_q:
        o = attn_mod.flash_attention(q, k, v, causal=True, window=window,
                                     chunk_q=cfg.attn_chunk_q,
                                     chunk_kv=cfg.attn_chunk_kv)
    else:
        o = attn_mod.dense_attention(q, k, v, causal=True, window=window)
    return o.reshape(b, s, h * hd) @ blk["wo"], (k, v)


def _mlp(blk: Dict, x: jnp.ndarray, cfg: ModelConfig):
    hn = rms_norm(x, blk["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        out, aux = moe_mod.moe_layer(blk["moe"], hn, cfg.top_k,
                                     cfg.capacity_factor,
                                     cfg.router_aux_weight)
        return out, aux
    return swiglu(hn, blk["w_gate"], blk["w_up"], blk["w_down"]), 0.0


def _attn_mlp_block(blk: Dict, x: jnp.ndarray, cfg: ModelConfig, window,
                    positions):
    a, kv_pair = _attention(blk, x, cfg, window, positions)
    x = _shard_act(x + a)
    m, aux = _mlp(blk, x, cfg)
    return _shard_act(x + m), aux, kv_pair


def _mamba_layer(layer: Dict, x: jnp.ndarray, cfg: ModelConfig,
                 return_state=False):
    hn = rms_norm(x, layer["ln"], cfg.norm_eps)
    if return_state:
        out, st = ssm_mod.mamba_block(layer["mamba"], hn, cfg,
                                      return_state=True)
        return _shard_act(x + out), st
    return _shard_act(x + ssm_mod.mamba_block(layer["mamba"], hn, cfg))


def _granite_attention(blk: Dict, x: jnp.ndarray, cfg: ModelConfig):
    """GQA with no position embedding, scores times attention_multiplier."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ blk["wq"]).reshape(b, s, h, hd)
    k = (x @ blk["wk"]).reshape(b, s, kv, hd)
    v = (x @ blk["wv"]).reshape(b, s, kv, hd)
    scale = cfg.attention_multiplier or None
    if cfg.use_flash and s > cfg.attn_chunk_q:
        o = attn_mod.flash_attention(q, k, v, causal=True,
                                     chunk_q=cfg.attn_chunk_q,
                                     chunk_kv=cfg.attn_chunk_kv, scale=scale)
    else:
        o = attn_mod.dense_attention(q, k, v, causal=True, scale=scale)
    return o.reshape(b, s, h * hd) @ blk["wo"]


def granite_layer(blk: Dict, x: jnp.ndarray, cfg: ModelConfig,
                  kind: str) -> jnp.ndarray:
    """One granitemoehybrid layer: pre-norm mixer and pre-norm (routed
    share + shared expert), each branch scaled by residual_multiplier."""
    hn = rms_norm(x, blk["ln1"], cfg.norm_eps)
    mix = (ssm_mod.mamba_block(blk["mamba"], hn, cfg) if kind == "mamba"
           else _granite_attention(blk, hn, cfg))
    x = _shard_act(x + mix * cfg.residual_multiplier)
    hn = rms_norm(x, blk["ln2"], cfg.norm_eps)
    sh = blk["shared"]
    ffn = (moe_mod.local_moe_layer(blk["moe"], hn, cfg.top_k,
                                   cfg.expert_offset, cfg.capacity_factor)
           + swiglu(hn, sh["w_gate"], sh["w_up"], sh["w_down"]))
    return _shard_act(x + ffn * cfg.residual_multiplier)


def _granite_forward(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray):
    x = _shard_act(params["embed"][tokens]
                   * jnp.asarray(cfg.embedding_multiplier, _dtype(cfg)))
    layers = []
    for kind in cfg.layer_types[:cfg.layer_period]:
        fn = partial(granite_layer, cfg=cfg, kind=kind)
        # per-layer remat: a layer's SSD chunk tensors are rebuilt in its
        # own backward, so only one layer's are live at a time
        layers.append(jax.checkpoint(fn) if cfg.remat else fn)

    def period(carry, blocks):
        for fn, blk in zip(layers, blocks):
            carry = fn(blk, carry)
        return carry, None

    x, _ = jax.lax.scan(period, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head) / jnp.asarray(cfg.logits_scaling, x.dtype)
    return ctx.constrain(logits, "batch", None, "model")


# ---------------------------------------------------------------------------
# Forward (training)
# ---------------------------------------------------------------------------
def _embed_inputs(params, cfg, tokens, prefix_embeds):
    x = params["embed"][tokens] * jnp.asarray(
        np.sqrt(cfg.d_model), _dtype(cfg))
    if cfg.frontend and prefix_embeds is not None:
        pre = prefix_embeds.astype(_dtype(cfg)) @ params["frontend_proj"]
        x = jnp.concatenate([pre, x], axis=1)
    return _shard_act(x)


def forward(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
            prefix_embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits over the token positions, aux losses)."""
    if cfg.family == "granitemoehybrid":
        return (_granite_forward(params, cfg, tokens),
                jnp.zeros((), jnp.float32))
    x = _embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, d = x.shape
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        def body(carry, layer):
            return _mamba_layer(layer, carry, cfg), None
        body = _remat(body, cfg)
        x, _ = jax.lax.scan(body, x, params["layers"])
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group(carry, layers):
            def inner(c, layer):
                return _mamba_layer(layer, c, cfg), None
            # nested remat: the SSD intra-chunk tensors of all attn_every
            # inner layers would otherwise be live at once in the group's
            # backward recompute
            inner = jax.checkpoint(inner) if cfg.remat else inner
            h, _ = jax.lax.scan(inner, carry, layers)
            h, aux, _ = _attn_mlp_block(shared, h, cfg, 0, positions)
            return h, aux
        group = _remat(group, cfg)
        x, auxs = jax.lax.scan(group, x, params["layers"])
        aux_total = aux_total + jnp.sum(auxs)
    else:
        windows = jnp.asarray(layer_windows(cfg))

        def body(carry, inp):
            layer, window = inp
            h, aux, _ = _attn_mlp_block(layer, carry, cfg, window, positions)
            return h, aux
        body = _remat(body, cfg)
        x, auxs = jax.lax.scan(body, x, (params["layers"], windows))
        aux_total = aux_total + jnp.sum(auxs)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.frontend and prefix_embeds is not None:
        x = x[:, prefix_embeds.shape[1]:]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = ctx.constrain(x @ head, "batch", None, "model")
    return logits, aux_total


def loss_fn(params: Dict, cfg: ModelConfig, batch: Dict) -> Tuple[jnp.ndarray,
                                                                  Dict]:
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("prefix_embeds"))
    ce = cross_entropy(logits, batch["labels"])
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with caches
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    dtype = _dtype(cfg)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    # per-slot positions: continuous batching keeps a length per sequence
    state: Dict[str, Any] = {"index": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "ssm":
        st = ssm_mod.init_mamba_state(cfg, batch, dtype)
        state["ssm_layers"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), st)
    elif cfg.family == "hybrid":
        n_groups = cfg.n_layers // cfg.attn_every
        st = ssm_mod.init_mamba_state(cfg, batch, dtype)
        state["ssm_layers"] = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (n_groups, cfg.attn_every) + x.shape), st)
        state["k"] = jnp.zeros((n_groups, batch, max_seq, kv, hd), dtype)
        state["v"] = jnp.zeros((n_groups, batch, max_seq, kv, hd), dtype)
    else:
        state["k"] = jnp.zeros((cfg.n_layers, batch, max_seq, kv, hd), dtype)
        state["v"] = jnp.zeros((cfg.n_layers, batch, max_seq, kv, hd), dtype)
    return state


def prefill(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
            max_seq: int, prefix_embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict]:
    """Run the prompt, returning (last-position logits, decode state)."""
    x = _embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, d = x.shape
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    state = init_decode_state(cfg, b, max_seq)
    state["index"] = jnp.full((b,), s, jnp.int32)

    def pad_kv(k):
        return jnp.pad(k, ((0, 0), (0, max_seq - s), (0, 0), (0, 0)))

    if cfg.family == "ssm":
        def body(carry, layer):
            h, st = _mamba_layer(layer, carry, cfg, return_state=True)
            return h, st
        x, states = jax.lax.scan(body, x, params["layers"])
        state["ssm_layers"] = states
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group(carry, layers):
            def inner(c, layer):
                return _mamba_layer(layer, c, cfg, return_state=True)
            h, sts = jax.lax.scan(inner, carry, layers)
            h, _, (k, v) = _attn_mlp_block(shared, h, cfg, 0, positions)
            return h, (sts, pad_kv(k), pad_kv(v))
        x, (sts, ks, vs) = jax.lax.scan(group, x, params["layers"])
        state["ssm_layers"] = sts
        state["k"], state["v"] = ks, vs
    else:
        windows = jnp.asarray(layer_windows(cfg))

        def body(carry, inp):
            layer, window = inp
            h, _, (k, v) = _attn_mlp_block(layer, carry, cfg, window,
                                           positions)
            return h, (pad_kv(k), pad_kv(v))
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], windows))
        state["k"], state["v"] = ks, vs

    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, state


def _decode_attention_block(blk: Dict, x: jnp.ndarray, cfg: ModelConfig,
                            window, index, k_cache, v_cache):
    """x: (B, 1, D); index: (B,) per-slot positions."""
    b = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    hn = rms_norm(x, blk["ln1"], cfg.norm_eps)
    q = (hn @ blk["wq"]).reshape(b, 1, h, hd)
    k = (hn @ blk["wk"]).reshape(b, 1, kv, hd)
    v = (hn @ blk["wv"]).reshape(b, 1, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = rms_norm(k, blk["k_norm"], cfg.norm_eps)
    pos = index[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    # per-slot cache insertion (each slot writes at its own position)
    upd = jax.vmap(
        lambda cb, kb, i: jax.lax.dynamic_update_slice_in_dim(cb, kb, i, 0))
    k_cache = upd(k_cache, k, index)
    v_cache = upd(v_cache, v, index)
    o = attn_mod.decode_attention(q, k_cache, v_cache, index, window)
    out = o.reshape(b, 1, h * hd) @ blk["wo"]
    m, _ = _mlp(blk, x + out, cfg)
    return x + out + m, k_cache, v_cache


def decode_step(params: Dict, cfg: ModelConfig, token: jnp.ndarray,
                state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One decode step.  token: (B, 1) int32 -> (logits (B,1,V), state)."""
    x = params["embed"][token] * jnp.asarray(
        np.sqrt(cfg.d_model), _dtype(cfg))
    index = state["index"]
    new_state = dict(state)

    if cfg.family == "ssm":
        def body(carry, inp):
            layer, st = inp
            hn = rms_norm(carry, layer["ln"], cfg.norm_eps)
            out, st2 = ssm_mod.mamba_decode_step(layer["mamba"], hn, st, cfg)
            return carry + out, st2
        x, sts = jax.lax.scan(body, x, (params["layers"],
                                        state["ssm_layers"]))
        new_state["ssm_layers"] = sts
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group(carry, inp):
            layers, sts, k_cache, v_cache = inp

            def inner(c, linp):
                layer, st = linp
                hn = rms_norm(c, layer["ln"], cfg.norm_eps)
                out, st2 = ssm_mod.mamba_decode_step(layer["mamba"], hn, st,
                                                     cfg)
                return c + out, st2
            h, sts2 = jax.lax.scan(inner, carry, (layers, sts))
            h, kc, vc = _decode_attention_block(shared, h, cfg, 0, index,
                                                k_cache, v_cache)
            return h, (sts2, kc, vc)
        x, (sts, ks, vs) = jax.lax.scan(
            group, x, (params["layers"], state["ssm_layers"], state["k"],
                       state["v"]))
        new_state["ssm_layers"] = sts
        new_state["k"], new_state["v"] = ks, vs
    else:
        windows = jnp.asarray(layer_windows(cfg))

        def body(carry, inp):
            layer, window, k_cache, v_cache = inp
            h, kc, vc = _decode_attention_block(layer, carry, cfg, window,
                                                index, k_cache, v_cache)
            return h, (kc, vc)
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], windows, state["k"], state["v"]))
        new_state["k"], new_state["v"] = ks, vs

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    new_state["index"] = index + 1
    return x @ head, new_state
