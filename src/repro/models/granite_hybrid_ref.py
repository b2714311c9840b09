"""Plain float32 reference of a granitemoehybrid (Granite 4.0-H) language
model: logits, loss and gradients, for checking ``models.transformer``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: one Python loop over the
layers (no scan stacking, no remat), the Mamba-2 mixer through the
sequential ``ssm.ssd_reference``, causal GQA with the whole score matrix,
the routed experts as a loop over the experts held, each run on every
token and weighted by its gate.  It follows the model as Hugging Face's
``granitemoehybrid`` publishes it (pre-norm mixer and MoE branches, each
times ``residual_multiplier``; embedding times ``embedding_multiplier``;
NoPE attention with scores times ``attention_multiplier``; gated RMSNorm
over the whole Mamba inner width; top-k of the router logits with a
softmax over the k chosen; a SiLU-gated shared expert on every token;
logits divided by ``logits_scaling``; tied embeddings), with these
departures:

* routing is not dropless: each expert takes at most
  ``round(T * K / E * capacity_factor)`` of a batch's assignments, in
  token order, and drops the rest (the rule of the program's dispatch,
  an assumption of the configuration);
* only the experts held (``expert_offset`` onward, ``n_experts_local`` of
  them) contribute: the part of the layer that one chip of an
  expert-parallel deployment computes, the router still over all;
* the vocabulary is what the configuration holds (a slice, when cut);
* no router auxiliary loss (Hugging Face adds it only with
  ``output_router_logits``, off by default);
* :func:`loss_and_grads_blocked` computes the same numbers layer by layer,
  and the SSD recurrence in time blocks recomputed in the backward pass,
  so that a full-width layer fits one chip; the mathematics is unchanged.

Parameters are the program's (``transformer.init_params``): the j-th
layer of period p is ``params["layers"][j]`` at index p.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.ssm import ssd_reference

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def layer_of(params: Dict, cfg: ModelConfig, i: int) -> Dict:
    """Layer ``i``'s parameters, as stored, out of the period stacks."""
    period = cfg.layer_period
    return jax.tree.map(lambda a: a[i // period], params["layers"][i % period])


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def mamba_mixer(p: Dict, x, cfg: ModelConfig,
                ssd_block: Optional[int] = None):
    """Mamba-2: in_proj, causal depthwise conv and SiLU on (x, B, C),
    dt = softplus(dt + bias), the SSD recurrence with the D skip, gated
    RMSNorm, out_proj.  ``ssd_block``: run the recurrence in blocks of
    that many steps, each recomputed in the backward pass."""
    b, l, _ = x.shape
    di, n, h, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = x @ p["in_proj"]
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * n],
                  proj[..., 2 * di + 2 * n:])
    k = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((b, k - 1, xbc.shape[-1]), F32), xbc], 1)
    conv = sum(xp[:, j:j + l] * p["conv_w"][j] for j in range(k))
    xbc = silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(b, l, h, hp)
    bm = xbc[..., di:di + n].reshape(b, l, 1, n)
    cm = xbc[..., di + n:].reshape(b, l, 1, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    if ssd_block is None or ssd_block >= l:
        y = ssd_reference(xs, dt, a, bm, cm, p["d_skip"])
    else:
        run = jax.checkpoint(
            lambda s0, *blk: ssd_reference(*blk[:2], a, *blk[2:],
                                           p["d_skip"], initial_state=s0,
                                           return_final=True))
        state = jnp.zeros((b, h, n, hp), F32)
        ys = []
        for t0 in range(0, l, ssd_block):
            sl = slice(t0, t0 + ssd_block)
            y_blk, state = run(state, xs[:, sl], dt[:, sl], bm[:, sl],
                               cm[:, sl])
            ys.append(y_blk)
        y = jnp.concatenate(ys, 1)
    y = rms_norm(y.reshape(b, l, di) * silu(z), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"]


def attention(p: Dict, x, cfg: ModelConfig):
    """Causal GQA, no position embedding, scores times
    ``attention_multiplier``: one KV head's query group at a time."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    rep = h // kv
    scale = cfg.attention_multiplier or hd ** -0.5
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, kv, hd)
    v = (x @ p["wv"]).reshape(b, s, kv, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for g in range(kv):
        qg = q[:, :, g * rep:(g + 1) * rep]                  # (b, s, rep, hd)
        scores = jnp.einsum("bqrd,bkd->brqk", qg, k[:, :, g]) * scale
        scores = jnp.where(causal, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("brqk,bkd->bqrd", probs, v[:, :, g]))
    return jnp.concatenate(outs, 2).reshape(b, s, h * hd) @ p["wo"]


def routed_experts(p: Dict, x, cfg: ModelConfig):
    """The held experts' part of the MoE output: top-k of the router
    logits, softmax over those k, each expert's assignments past its
    capacity (in token order) dropped, each held expert run on every
    token and weighted by its gate where kept."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    n_experts = p["router"].shape[1]
    logits = xt @ p["router"]
    top_l, top_e = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top_l, -1)                        # (t, K)
    capacity = int(max(1, round(t * cfg.top_k / n_experts
                                * cfg.capacity_factor)))
    out = jnp.zeros((t, d), F32)
    for j in range(p["w_gate"].shape[0]):
        chosen = top_e == cfg.expert_offset + j              # (t, K)
        # the slot each assignment takes: how many came before it
        order = jnp.cumsum(chosen.reshape(-1)).reshape(t, cfg.top_k) - 1
        weight = jnp.sum(jnp.where(chosen & (order < capacity), gates, 0.0),
                         -1)
        hidden = silu(xt @ p["w_gate"][j]) * (xt @ p["w_up"][j])
        out = out + weight[:, None] * (hidden @ p["w_down"][j])
    return out.reshape(b, s, d)


def shared_expert(p: Dict, x):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def layer(p: Dict, x, cfg: ModelConfig, kind: str,
          ssd_block: Optional[int] = None):
    """One layer, parameters in float32."""
    hn = rms_norm(x, p["ln1"], cfg.norm_eps)
    mix = (mamba_mixer(p["mamba"], hn, cfg, ssd_block) if kind == "mamba"
           else attention(p, hn, cfg))
    x = x + cfg.residual_multiplier * mix
    hn = rms_norm(x, p["ln2"], cfg.norm_eps)
    ffn = routed_experts(p["moe"], hn, cfg) + shared_expert(p["shared"], hn)
    return x + cfg.residual_multiplier * ffn


def embed(table, tokens, cfg: ModelConfig):
    return table[tokens] * cfg.embedding_multiplier


def head(final_norm, table, x, cfg: ModelConfig):
    return rms_norm(x, final_norm, cfg.norm_eps) @ table.T / cfg.logits_scaling


def cross_entropy(logits, labels):
    logz = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - picked)


def forward(params: Dict, cfg: ModelConfig, tokens) -> jnp.ndarray:
    """Logits (B, S, V) in float32."""
    with jax.default_matmul_precision("highest"):
        x = embed(jnp.asarray(params["embed"], F32), tokens, cfg)
        for i in range(cfg.n_layers):
            x = layer(_f32(layer_of(params, cfg, i)), x, cfg,
                      cfg.layer_types[i])
        return head(jnp.asarray(params["final_norm"], F32),
                    jnp.asarray(params["embed"], F32), x, cfg)


def loss(params: Dict, cfg: ModelConfig, tokens, labels) -> jnp.ndarray:
    with jax.default_matmul_precision("highest"):
        return cross_entropy(forward(params, cfg, tokens), labels)


def loss_and_grads(params: Dict, cfg: ModelConfig, tokens, labels):
    """(loss, gradients shaped as ``params``), in float32."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(_f32(params), cfg, tokens, labels)


GradPick = Tuple[int, Sequence[str]]    # (layer, key path); layer -1: embed


def loss_and_grads_blocked(params: Dict, cfg: ModelConfig, tokens, labels,
                           picks: Dict[str, GradPick],
                           ssd_block: Optional[int] = None
                           ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """:func:`loss_and_grads` one layer at a time: the forward keeps each
    layer's input, the backward runs each layer's VJP from the last, and
    only the gradients ``picks`` names are kept (``(-1, ())`` is the
    embedding).  Layers are cast to float32 as they are used, so memory
    holds ``params`` as given, one layer in float32 and the activations."""
    kinds = cfg.layer_types[:cfg.n_layers]

    @jax.jit
    def first(table):
        return embed(jnp.asarray(table, F32), tokens, cfg)

    def fwd(kind):
        return jax.jit(lambda p, x: layer(p, x, cfg, kind, ssd_block))

    def bwd(kind):
        def run(p, x, g):
            _, vjp = jax.vjp(lambda p, x: layer(p, x, cfg, kind, ssd_block),
                             p, x)
            return vjp(g)
        return jax.jit(run)

    fwds = {k: fwd(k) for k in set(kinds)}
    bwds = {k: bwd(k) for k in set(kinds)}

    with jax.default_matmul_precision("highest"):
        xs = [first(params["embed"])]
        for i, kind in enumerate(kinds):
            xs.append(fwds[kind](_layer_f32(params, cfg, i), xs[-1]))
        table = jnp.asarray(params["embed"], F32)
        value, (g_table, g_x) = jax.jit(jax.value_and_grad(
            lambda table, x: cross_entropy(
                head(jnp.asarray(params["final_norm"], F32), table, x, cfg),
                labels), argnums=(0, 1)))(table, xs[-1])
        out: Dict[str, jnp.ndarray] = {}
        for i in reversed(range(len(kinds))):
            g_p, g_x = bwds[kinds[i]](_layer_f32(params, cfg, i), xs[i], g_x)
            for name, (li, path) in picks.items():
                if li == i:
                    g = g_p
                    for key in path:
                        g = g[key]
                    out[name] = g
        # the embedding: the head's use plus the lookup's scatter
        g_table = g_table.at[tokens].add(g_x * cfg.embedding_multiplier)
        for name, (li, _) in picks.items():
            if li == -1:
                out[name] = g_table
    return value, out


@jax.jit
def _slice_f32(stacked, index):
    return jax.tree.map(lambda a: jnp.asarray(a[index], F32), stacked)


def _layer_f32(params: Dict, cfg: ModelConfig, i: int) -> Dict:
    return _slice_f32(params["layers"][i % cfg.layer_period],
                      i // cfg.layer_period)


def grad_of(grads: Dict, cfg: ModelConfig, pick: GradPick):
    """The gradient ``pick`` names in a program-shaped gradient tree."""
    li, path = pick
    if li == -1:
        return grads["embed"]
    g = layer_of(grads, cfg, li)
    for key in path:
        g = g[key]
    return g


def picks_one_per_kind(cfg: ModelConfig) -> Dict[str, GradPick]:
    """A gradient per kind of layer and part: the first Mamba mixer's
    in_proj, the first attention layer's query projection, the last
    layer's routed-expert down projection and router, the first layer's
    shared expert, and the embedding."""
    kinds = list(cfg.layer_types[:cfg.n_layers])
    first_m, first_a = kinds.index("mamba"), kinds.index("attention")
    last = len(kinds) - 1
    return {"mamba.in_proj": (first_m, ("mamba", "in_proj")),
            "attention.wq": (first_a, ("wq",)),
            "experts.w_down": (last, ("moe", "w_down")),
            "router": (last, ("moe", "router")),
            "shared.w_up": (0, ("shared", "w_up")),
            "embed": (-1, ())}
