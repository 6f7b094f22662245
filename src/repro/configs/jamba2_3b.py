"""jamba2-3b [hybrid] — AI21-Jamba2-3B: Mamba-1 + attention, 13:1, dense FFN.

Source: https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json
28L d_model=2560, 20 query heads of 128 with ONE kv head (MQA), no
positional encoding (the config has no rope key; arXiv:2403.19887),
SiLU-gated FFN of 8192 in every layer (num_experts 1 = dense), vocab
65536, tied embeddings, RMSNorm eps 1e-6, unscaled embeddings.  Mamba:
d_state 16, d_conv 4 (with bias), expand 2 (d_inner 5120), dt_rank 160,
dt/B/C RMSNorms, no projection bias.  Attention where
i % attn_layer_period (14) == attn_layer_offset (7): layers 7 and 21.
The order of the layer types is not in the catalog; HF Jamba's
convention is assumed.
"""
from repro.models.base import ArchConfig

CONFIG = ArchConfig(
    name="jamba2-3b",
    family="hybrid",
    source="https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json",
    num_layers=28,
    d_model=2560,
    d_ff=8192,
    vocab_size=65_536,
    num_heads=20,
    num_kv_heads=1,
    head_dim=128,
    use_rope=False,
    attn_every=14,
    attn_offset=7,
    mamba_d_state=16,
    mamba_d_conv=4,
    mamba_expand=2,
    mamba_dt_rank=160,
    tie_embeddings=True,
    scale_embeddings=False,
    norm_eps=1e-6,
)
