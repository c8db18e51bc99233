"""granite-4.0-h-small — hybrid Mamba-2 / NoPE-attention MoE, one chip's
share [hf:ibm-granite/granite-4.0-h-small, config.json].

Published (https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json,
IBM Granite 4.0-H Small, 32B-A9B): 40 layers, d_model 4096; Mamba-2
everywhere except attention at layers 5, 15, 25 and 35 (one period of
10: nine Mamba-2 layers, then attention at index 5); Mamba-2 with 128
heads of 64 (d_inner 8192), d_state 128, 1 group, conv 4 with bias,
chunk 256, no projection bias; GQA 32/8 heads of 128 with no position
embedding, softmax scale 0.0078125, no bias; in every layer an MoE of 72
SwiGLU experts of width 768, top-10 with the gates a softmax over the
ten, plus one always-active shared SwiGLU expert of width 1536;
embedding x12, both residual branches x0.22, logits / 16; RMSNorm eps
1e-5; vocab 100352, tied embeddings; context 131072.

One chip's share (this entry): the deployment splits each layer's 72
experts over 8 chips by expert parallelism, 9 per chip, with the mixers,
router, shared expert and vocabulary whole on every chip.  This chip
holds experts 0-8 and one whole period: 10 of the 40 layers, the other
30 on further pipeline stages.  The router still scores all 72 experts
and picks ten; the absent experts' part is left out.  2.42 B parameters.
"""

from .base import ModelConfig, MoEConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    arch_type="hybrid",
    num_layers=10,                 # one period of the published 40
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,                      # config.json's intermediate_size: the
                                   # routed experts' width
    vocab_size=100352,
    nope=True,
    attn_scale=0.0078125,
    attn_period=10,
    attn_offset=5,
    moe=MoEConfig(num_experts=72, num_experts_per_tok=10,
                  num_shared_experts=1, shared_d_ff=1536,
                  num_local_experts=9),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, chunk=256,
                  conv_width=4, n_groups=1),
    norm_type="rmsnorm",
    rms_norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_embeddings=True,
    max_position=131072,
    dtype="bfloat16",
    source="hf:ibm-granite/granite-4.0-h-small",
    long_context_ok=True,          # 1 attention layer in 10; O(1) state
    notes="one chip of 8-way expert parallelism: experts 0-8 of 72, "
          "layers 0-9 of 40",
)
