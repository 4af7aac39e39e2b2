"""granite-4.0-h-small [hybrid]: Mamba-2 and NoPE GQA layers, each followed
by a 72-expert top-10 MoE and a shared expert.

40L d_model=4096; layer_types: attention at layers 5, 15, 25 and 35, Mamba-2
elsewhere, i.e. four repeats of the 10-layer period (5 Mamba-2, 1 attention,
4 Mamba-2). Mamba-2: 128 heads of 64 (d_inner 8192), d_state 128, one
group, conv 4 with bias, chunk 256. Attention: 32 query and 8 KV heads of
128, no positional encoding, scale ``attention_multiplier`` 1/128. Every
layer's FFN: top-10 of 72 routed SwiGLU experts of width 768 (softmax over
the 10 chosen logits) plus a shared SwiGLU expert of width 1536, which is
the ``moe_dense_residual`` MLP beside the experts. Embeddings times 12, each
branch times 0.22 before its residual, logits divided by 16. Tied
embeddings, vocab 100352, RMSNorm eps 1e-5
[https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json].
"""
from repro.configs.base import ModelConfig

PERIOD = ("ssd",) * 5 + ("global",) + ("ssd",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=1536,
    vocab_size=100352,
    pattern=PERIOD,
    query_scale=1.0 / 128,
    use_rope=False,
    mlp_activation="swiglu",
    num_experts=72,
    num_experts_per_tok=10,
    moe_d_ff=768,
    moe_dense_residual=True,
    ssm_state_dim=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    conv_width=4,
    ssd_ffn=True,
    tie_embeddings=True,
    embed_scale=False,
    embed_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    supports_long_context=False,
)
