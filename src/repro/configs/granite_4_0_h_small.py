"""granite-4.0-h-small [hybrid] — Mamba-2 mixers with one NoPE GQA layer
in ten, a routed and a shared SwiGLU expert on every layer, and Granite's
scalar multipliers.
[hf:ibm-granite/granite-4.0-h-small config.json]

40L d_model=4096, layer_types period 10: 9 Mamba-2 mixers (128 heads of
64, d_state 128, n_groups 1, conv 4, chunk 256, expand 2: d_inner 8192,
output through the gated RMSNorm) and attention at position 5 (32H over
GQA kv=8, head_dim 128, no positional encoding, scores scaled by
attention_multiplier 1/128).  Every layer: 72 routed experts of width 768,
top-10, plus an always-on shared expert of width 1536.  Residual x0.22 on
both sublayers, embedding x12, logits /16; tied vocab 100352; RMSNorm eps
1e-5.  32.2B parameters, 8.8B active a token.
"""
from repro.models.common import ArchConfig, LayerSpec

ARCH_ID = "granite-4.0-h-small"


def config() -> ArchConfig:
    pattern = tuple(
        LayerSpec(kind="attn" if i == 5 else "mamba", attn="causal",
                  mlp="moe")
        for i in range(10))
    return ArchConfig(
        name=ARCH_ID,
        family="hybrid",
        n_layers=40,
        d_model=4096,
        n_heads=32,
        n_kv_heads=8,
        d_ff=768,
        vocab=100352,
        head_dim=128,
        n_experts=72,
        top_k=10,
        shared_expert=True,
        d_ff_shared=1536,
        position_embedding="nope",
        attention_multiplier=0.0078125,
        ssm_state=128,
        ssm_heads=128,       # d_inner 8192 / P 64
        ssm_expand=2,
        ssm_chunk=256,
        conv_width=4,
        ssm_gated_norm=True,
        norm_eps=1e-5,
        tie_embeddings=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        pattern=pattern,
    )
