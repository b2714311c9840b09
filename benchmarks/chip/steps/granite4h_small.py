"""Granite 4.0-H Small's training step, cut to one chip of its deployment
(``configs/granite4h-small.json``): what a user traces and sends the
service.

The step is the program's own model code (``repro.models.transformer``,
family ``granitemoehybrid``): one period of the published layer pattern
(9 Mamba-2 layers and 1 NoPE GQA layer, in published order), each layer
followed by this chip's 9 of the 72 routed experts (routed over all 72,
top-10) and the shared expert, every width as published; the vocabulary
slice; softmax cross-entropy over it; every layer rematerialised in the
backward pass; one SGD update.

``make_step(config, batch)`` returns ``(step, params, data)`` with
``params`` and ``data`` as shapes only: the corpus is tracked, not run.
``model_config(config)`` is the program's ``ModelConfig`` of the cut.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LR = 1e-3


def model_config(cfg: dict):
    from repro.models.config import ModelConfig

    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    n_layers = cfg["num_hidden_layers"]
    dep = cfg["deployment"]
    out = ModelConfig(
        name=cfg["name"], family="granitemoehybrid",
        n_layers=n_layers, d_model=d, n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d // heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        layer_types=tuple(cfg["layer_types"][:n_layers]),
        shared_ff=cfg["shared_intermediate_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        n_experts=dep["experts_routed"],
        n_experts_local=cfg["num_local_experts"],
        expert_offset=dep["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        capacity_factor=cfg["capacity_factor"],
        ssm_state=cfg["mamba_d_state"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_conv=cfg["mamba_d_conv"], ssm_expand=cfg["mamba_expand"],
        ssm_chunk=cfg["mamba_chunk_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"],
        param_dtype=cfg["dtype"], act_dtype=cfg["dtype"], remat=True)
    # what the program's Mamba-2 block derives must be what is published
    assert cfg["mamba_n_groups"] == out.ssm_groups == 1
    assert cfg["mamba_n_heads"] == out.ssm_heads
    assert cfg["position_embedding_type"] == "nope"
    assert not (cfg["attention_bias"] or cfg["mamba_proj_bias"])
    assert cfg["mamba_conv_bias"] and cfg["hidden_act"] == "silu"
    return out


def make_step(cfg: dict, batch: int):
    from repro.models import transformer

    mcfg = model_config(cfg)

    def step(params, data):
        value, grads = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, mcfg, data)[0])(params)
        return value, jax.tree.map(lambda p, g: p - LR * g, params, grads)

    params = jax.eval_shape(
        lambda: transformer.init_params(mcfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((batch, cfg["seq_len"]), jnp.int32)
    return step, params, {"tokens": tokens, "labels": tokens}
