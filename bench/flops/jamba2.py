"""Operations one INTERACT agent step needs on the jamba2 share, counted
from the shapes (a multiply and an add count as 2).

Per token of a batch, forward through the held share (T tokens, d model
width, H held query heads of hd, one kv head, d_in held Mamba channels,
R dt_rank, N d_state, F held FFN columns, V held vocabulary rows):

* Mamba layer: in_proj 2 d 2 d_in, x_proj 2 d_in (R + 2 N), dt_proj
  2 R d_in, out_proj 2 d_in d, the scan's C . h contraction 2 d_in N,
  the causal conv 2 d_conv d_in;
* attention layer: q and o projections 2 d H hd each, k and v 2 d hd
  each, and the causal core QK^T and PV over T/2 keys on average,
  2 T H hd;
* FFN (every layer): 3 products of 2 d F;
* head: one logit product 2 d V (the "head unit").

Per agent step (eqs. 5-10 of arXiv 2207.13283, ``repro.hypergrad.head``):
the outer batch's forward, its backward (twice the forward: weight and
input gradients), the inner batch's forward and the cross term's
backward: 6 backbone forwards; head units: 3 for grad f (logits, d_F,
d_y), 2 for the linearized grad_y g (logits, F^T (P - Y)), 2 for each of
the K - 1 head-space HVPs, 3 for the cross term's d_F (F z and the two
transposed products); the two mixes of x and u, 5 per entry each
(three weighted terms on a ring).  Recomputation under remat, the
attention blocks above the diagonal and elementwise work are left out,
so this is a floor of what any implementation must do.
"""
from __future__ import annotations


def backbone_forward_per_token(conf: dict, tokens: int) -> float:
    tp = conf["tensor_parallel"]
    d, hd = conf["hidden_size"], conf["head_dim"]
    heads = conf["num_attention_heads"]
    d_in = conf["mamba_expand"] * d // tp
    rank, n = conf["mamba_dt_rank"], conf["mamba_d_state"]
    ff = conf["intermediate_size"] // tp
    mamba = (2 * d * 2 * d_in + 2 * d_in * (rank + 2 * n) + 2 * rank * d_in
             + 2 * d_in * d + 2 * d_in * n + 2 * conf["mamba_d_conv"] * d_in)
    attn = 2 * 2 * d * heads * hd + 2 * 2 * d * hd + 2 * tokens * heads * hd
    ffn = 3 * 2 * d * ff
    layers = conf["num_hidden_layers"]
    n_attn = layers // conf["attn_layer_period"]
    return (layers - n_attn) * mamba + n_attn * attn + layers * ffn


def backbone_params(conf: dict) -> float:
    tp = conf["tensor_parallel"]
    d, hd = conf["hidden_size"], conf["head_dim"]
    d_in = conf["mamba_expand"] * d // tp
    rank, n = conf["mamba_dt_rank"], conf["mamba_d_state"]
    ff = conf["intermediate_size"] // tp
    mamba = (2 * d * d_in + conf["mamba_d_conv"] * d_in + d_in
             + d_in * (rank + 2 * n) + rank + 2 * n + rank * d_in
             + 3 * d_in + d_in * n + d_in * d)
    attn = 2 * d * conf["num_attention_heads"] * hd + 2 * d * hd
    layers = conf["num_hidden_layers"]
    n_attn = layers // conf["attn_layer_period"]
    return ((layers - n_attn) * mamba + n_attn * attn
            + layers * (3 * d * ff + 2 * d) + conf["vocab_size"] * d + d)


def interact_agent_step(conf: dict, params: dict) -> float:
    tokens = params["batch"] * params["seq_len"]
    fwd = tokens * backbone_forward_per_token(conf, params["seq_len"])
    head = 2.0 * tokens * conf["hidden_size"] * conf["vocab_size"]
    k = conf["bilevel"]["neumann_k"]
    head_units = 3 + 2 + 2 * (k - 1) + 3
    mix = 2 * 5 * backbone_params(conf)
    return 6 * fwd + head_units * head + mix
