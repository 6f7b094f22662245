"""Pallas TPU flash-attention kernel (GQA / causal / SWA / softcap).

Blockwise streaming-softmax attention with explicit VMEM tiling:

  grid = (batch, q_heads, num_q_blocks, num_kv_blocks)   kv innermost
  layout: (batch, heads, seq, head_dim), so every block's last two dims
          are (BLOCK, head_dim) — the (8, 128) tiling Mosaic requires —
          and the head is a grid index, never a size-1 block dim
  q block:  (BLOCK_Q, head_dim)  VMEM
  k,v blocks: (BLOCK_K, head_dim) VMEM (indexed by kv head = h // group)
  scratch: running (acc, m, l) in VMEM, persisted across the kv grid dim.

The online-softmax recurrence (Dao et al.) is adapted to the MXU: the two
matmuls per block (q k^T and p v) are jnp.dot on (BLOCK_Q, head_dim) x
(head_dim, BLOCK_K) tiles — multiples of 128 on the contracting and output
dims for MXU alignment (head_dim 64 archs use 64, still lane-aligned).

Causal + sliding-window masking is done with global row/col indices built
from the block coordinates; fully-masked kv blocks are skipped via
``pl.when`` so SWA costs O(seq * window), not O(seq^2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int | None,
                  logit_softcap: float | None, block_q: int, block_k: int,
                  q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Global positions of this block's rows/cols.  q_offset supports
    # decode/suffix queries whose absolute position starts mid-sequence.
    row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # Block-level skip: is any (row, col) pair in this tile visible?
    row_last = (qi + 1) * block_q - 1 + q_offset
    col_first = ki * block_k
    visible = jnp.bool_(True)
    if causal:
        visible = jnp.logical_and(visible, col_first <= row_last)
    if window is not None:
        row_first = qi * block_q + q_offset
        col_last = (ki + 1) * block_k - 1
        visible = jnp.logical_and(visible, col_last > row_first - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, row >= col)
        if window is not None:
            mask = jnp.logical_and(mask, row - col < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,  # (batch, num_heads, q_len, head_dim)
    k: jax.Array,  # (batch, num_kv_heads, kv_len, head_dim)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    logit_softcap: float | None = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """pallas_call wrapper on head-major (b, h, s, d) tensors.  Sequence
    lengths must be block multiples (ops.py transposes and pads)."""
    b, nh, sq, hd = q.shape
    _, nkv, skv, _ = k.shape
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv)
    group = nh // nkv
    scale = 1.0 / (hd ** 0.5)

    grid = (b, nh, sq // block_q, skv // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        logit_softcap=logit_softcap, block_q=block_q, block_k=block_k,
        q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
