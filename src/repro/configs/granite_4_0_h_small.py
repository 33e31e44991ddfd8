"""granite-4.0-h-small [hybrid]: 40 layers d_model=4096, 36 Mamba2 (128
heads of 64, state 128, one group, conv 4 with bias) and 4 GQA attention
layers with no position encoding (32H, kv=8, scale 1/128) at 5, 15, 25, 35;
every layer's mixer is followed by 72 routed SwiGLU experts of 768 (top-10,
softmax over the 10) and a shared SwiGLU expert of 1536; embedding x12,
residual branches x0.22, logits /16; RMSNorm eps 1e-5; vocab=100352, tied.
[hf:ibm-granite/granite-4.0-h-small config.json, model_type granitemoehybrid]
"""

import dataclasses

from .base import ModelConfig

MAMBA = ("mamba2", "experts")
ATTENTION = ("attention", "experts")
#: Attention at layers 5, 15, 25, 35: a period of ten layers.
PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, d_head=128,
    d_ff=768, vocab_size=100352,
    position_embedding_type="nope", attention_multiplier=0.0078125,
    rms_norm_eps=1e-5,
    n_experts=72, top_k=10, d_expert=768, d_shared_expert=1536,
    ssm_state=128, ssm_d_head=64, ssm_chunk=256, ssm_conv_bias=True,
    layer_types=PERIOD * 4,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
)


def smoke_config() -> ModelConfig:
    """Two periods of (Mamba2, attention); 32 routed experts of which 4
    are held (the published share of 9 in 72), top-8; the published
    multipliers.  The vocabulary is 12544 so that, at four layers of 128,
    a token's own embedding (x12) stands about as far above the other
    logits as at the published width and the share's depth; at 512 it
    stands so far above that every model, the int8 control too, serves
    the last token again."""
    return dataclasses.replace(
        CONFIG, n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, d_head=32,
        d_ff=64, vocab_size=12544, attention_multiplier=1 / 32,
        n_experts=32, top_k=8, d_expert=64, d_shared_expert=96,
        experts_held=4, ssm_state=16, ssm_d_head=16, ssm_chunk=8,
        layer_types=(MAMBA, ATTENTION) * 2, attn_chunk=32, loss_chunk=32)
