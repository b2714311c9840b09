"""granite-4.0-h-small [hf:ibm-granite/granite-4.0-h-small config.json]:
hybrid Mamba-2 / attention with fine-grained MoE in every layer.

40L d_model=4096: 36 Mamba-2 layers (128 heads of 64, d_state 128, one
group, conv 4, expand 2, chunk 256) and 4 GQA attention layers (32 query
heads, 8 KV heads, NoPE, scores scaled by 1/128) at 5, 15, 25 and 35;
every layer has 72 experts of width 768 (top-10, softmax over the chosen
logits) plus one shared expert of width 1536, SiLU-gated.  RMSNorm eps
1e-5; embedding x12, residual branches x0.22, logits /16; tied
embeddings, vocab 100352.  Published routing is dropless: the capacity
factor is this repo's dispatch rule, an assumption.
"""

from repro.models.config import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="granitemoehybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, vocab_size=100352,
    layer_types=_PERIOD * 4, shared_ff=1536,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0,
    n_experts=72, top_k=10, capacity_factor=1.25,
    ssm_state=128, ssm_head_dim=64, ssm_conv=4, ssm_expand=2,
    ssm_chunk=256,
    tie_embeddings=True, norm_eps=1e-5,
    param_dtype="bfloat16", act_dtype="bfloat16", remat=True,
)
