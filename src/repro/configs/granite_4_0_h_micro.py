"""granite-4.0-h-micro — Mamba2 mixers with NoPE GQA attention at every
10th layer (5, 15, 25, 35), a SwiGLU MLP after every layer.
[hf:ibm-granite/granite-4.0-h-micro]

The program runs the llama form of the block; granite's four multipliers
(embedding 12, attention 1/64, residual 0.22, logits 8) are folded into the
weights that are served (bench/drivers/decode_hybrid.py).
"""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-micro",
    family="mamba_attn",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,  # shared_intermediate_size (no experts)
    vocab=100352,
    rope=False,  # position_embedding_type "nope"
    attn_layers=(5, 15, 25, 35),
    ssm_state=128,
    ssm_heads=64,  # mamba_expand 2 x 2048 / mamba_d_head 64
    ssm_expand=2,
    ssm_conv=4,
    ssm_conv_bias=True,
    tie_embeddings=True,
    source="hf:ibm-granite/granite-4.0-h-micro",
)
