"""jit'd public wrapper for the flash attention kernel.

Handles padding of q/kv lengths to block multiples and picks block sizes
that keep the working set inside VMEM:

  VMEM bytes/step ~ block_q*hd*4 (q) + 2*block_k*hd*4 (k, v)
                  + block_q*hd*4 (acc) + block_q*block_k*4 (s/p tile)
  with (128, 128) and hd=256: ~0.6 MB — comfortably under the ~16 MB/core
  budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (
    DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_kernel)


def _pad_to(x: jax.Array, length: int, axis: int) -> jax.Array:
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_softcap", "q_offset",
                     "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
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
    """Attention on (batch, seq, heads, head_dim) tensors, as the models
    hold them; the kernel runs head-major, so q/k/v are transposed in and
    the output back out."""
    b, sq, nh, hd = q.shape
    skv = k.shape[1]
    block_q = min(block_q, max(8, sq))
    block_k = min(block_k, max(8, skv))
    sq_p = -(-sq // block_q) * block_q
    skv_p = -(-skv // block_k) * block_k
    head_major = lambda t: jnp.swapaxes(t, 1, 2)
    qp = _pad_to(head_major(q), sq_p, 2)
    kp = _pad_to(head_major(k), skv_p, 2)
    vp = _pad_to(head_major(v), skv_p, 2)
    # Padded kv columns must never be attended to.  Causal masking already
    # hides them from real rows when q and kv are co-indexed; for the
    # decode path (q_offset > 0) the window/causal mask built from global
    # positions does the same because padded cols have col > real rows only
    # when col > q_offset + sq - 1 >= every real row.
    out = flash_attention_kernel(
        qp, kp, vp, causal=causal, window=window,
        logit_softcap=logit_softcap, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return jnp.swapaxes(out[:, :, :sq], 1, 2)
