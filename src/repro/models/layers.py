"""Shared transformer layers: RMSNorm, RoPE, GQA attention, gated MLP.

All functions are pure; parameters are plain dicts of arrays.  The
attention here is the *reference* (jnp) implementation — the Pallas flash
kernel in ``repro/kernels`` is numerically validated against
``attention_ref`` and selected with ``attn_impl='pallas'`` at model level.

Supported attention variants (everything the assigned archs need):
  * grouped-query (num_kv_heads < num_heads), MQA (kv=1)
  * causal masking, sliding-window (mixtral, gemma2-local)
  * attention-logit softcapping (gemma2)
  * per-head q/k RMSNorm (qwen3)
  * single-token decode against a KV cache
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing

__all__ = [
    "rms_norm", "init_rms_norm",
    "rope_frequencies", "apply_rope",
    "init_attention", "attention_ref", "attention",
    "init_mlp", "gated_mlp",
    "softcap", "matmul", "einsum",
]

Params = dict


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """The model's projections go through here (and ``einsum``), so a
    precision control can round every product's inputs in one place."""
    return jnp.matmul(a, b)


def einsum(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b)


def init_rms_norm(d: int, dtype) -> Params:
    return {"scale": jnp.zeros((d,), dtype=dtype)}


def rms_norm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + params["scale"].astype(jnp.float32))).astype(dtype)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., s, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, qk_norm: bool, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(num_heads * head_dim)
    p = {
        "wq": (jax.random.normal(k1, (d_model, num_heads, head_dim)) * scale_in).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, num_kv_heads, head_dim)) * scale_in).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, num_kv_heads, head_dim)) * scale_in).astype(dtype),
        "wo": (jax.random.normal(k4, (num_heads, head_dim, d_model)) * scale_out).astype(dtype),
    }
    if qk_norm:
        p["q_norm"] = init_rms_norm(head_dim, dtype)
        p["k_norm"] = init_rms_norm(head_dim, dtype)
    return p


def _attn_mask(q_pos: jax.Array, k_pos: jax.Array,
               window: int | None) -> jax.Array:
    """(q, k) boolean mask: causal, optionally sliding-window."""
    causal = q_pos[:, None] >= k_pos[None, :]
    if window is None:
        return causal
    return causal & (q_pos[:, None] - k_pos[None, :] < window)


def attention_ref(
    q: jax.Array,            # (batch, q_len, heads, head_dim)
    k: jax.Array,            # (batch, kv_len, kv_heads, head_dim)
    v: jax.Array,            # (batch, kv_len, kv_heads, head_dim)
    q_positions: jax.Array,  # (q_len,)
    kv_positions: jax.Array, # (kv_len,)
    window: int | None = None,
    logit_softcap: float | None = None,
    kv_valid: jax.Array | None = None,  # (kv_len,) bool
) -> jax.Array:
    """Exact softmax GQA attention (the oracle for the flash kernel)."""
    b, qlen, nh, hd = q.shape
    nkv = k.shape[2]
    group = nh // nkv
    qg = q.reshape(b, qlen, nkv, group, hd)
    logits = einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    logits = softcap(logits, logit_softcap)
    mask = _attn_mask(q_positions, kv_positions, window)
    if kv_valid is not None:
        mask = mask & kv_valid[None, :]
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, qlen, nh, hd).astype(q.dtype)


def attention_blockwise(
    q: jax.Array,            # (batch, q_len, heads, head_dim)
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    window: int | None = None,
    logit_softcap: float | None = None,
    block_k: int = 1024,
) -> jax.Array:
    """Streaming-softmax attention: lax.scan over kv blocks.

    The XLA-side realisation of the flash algorithm: never materialises
    the (q_len, kv_len) score matrix — peak attention memory drops from
    O(s^2) to O(s * block_k).  Numerically identical to ``attention_ref``
    (same online-softmax recurrence as the Pallas kernel, which remains
    the TPU-optimal path; this one exists so *lowered* programs that
    cannot call Pallas (dry-run / CPU) get the same asymptotics).
    """
    b, sq, nh, hd = q.shape
    _, skv, nkv, _ = k.shape
    group = nh // nkv
    scale = 1.0 / float(hd) ** 0.5
    pad = (-skv) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, pad),),
                               constant_values=jnp.iinfo(jnp.int32).max)
    nblocks = k.shape[1] // block_k
    kb = k.reshape(b, nblocks, block_k, nkv, hd)
    vb = v.reshape(b, nblocks, block_k, nkv, hd)
    pb = kv_positions.reshape(nblocks, block_k)
    qg = q.reshape(b, sq, nkv, group, hd).astype(jnp.float32)

    def block(carry, xs):
        acc, mx, lse = carry
        kc, vc, pc = xs
        s = einsum("bqkgd,bskd->bkgqs", qg,
                   kc.astype(jnp.float32)) * scale
        s = softcap(s, logit_softcap)
        mask = q_positions[:, None] >= pc[None, :]
        if window is not None:
            mask &= q_positions[:, None] - pc[None, :] < window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(mx, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mx - m_new)
        lse = lse * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + einsum(
            "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        return (acc, m_new, lse), None

    acc0 = jnp.zeros((b, nkv, group, sq, hd), jnp.float32)
    m0 = jnp.full((b, nkv, group, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, nkv, group, sq), jnp.float32)
    (acc, _, lse), _ = jax.lax.scan(
        jax.checkpoint(block),
        (acc0, m0, l0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), pb))
    out = acc / jnp.maximum(lse, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, nh, hd)
    return out.astype(q.dtype)


@tracing.scoped(tracing.ATTN)
def attention(params: Params, x: jax.Array, positions: jax.Array, *,
              num_heads: int, num_kv_heads: int, head_dim: int,
              rope_theta: float | None, window: int | None,
              logit_softcap: float | None, qk_norm: bool, norm_eps: float,
              cache: dict | None = None, impl: str = "reference") -> tuple[jax.Array, dict | None]:
    """Full attention layer: qkv projection, rope, SDPA, out projection.

    ``cache`` (decode): {"k": (b, max_len, kv, hd), "v": ..., "len": int32}
    — the new token is written at index ``len`` and attends to the prefix.
    ``rope_theta=None``: no positional encoding (jamba's attention).
    """
    b, s, d = x.shape
    q = einsum("bsd,dhk->bshk", x, params["wq"])
    k = einsum("bsd,dhk->bshk", x, params["wk"])
    v = einsum("bsd,dhk->bshk", x, params["wv"])
    if qk_norm:
        q = rms_norm(params["q_norm"], q, norm_eps)
        k = rms_norm(params["k_norm"], k, norm_eps)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    if cache is not None and s > 1:
        # Prefill into a fresh cache: attend among the new tokens exactly,
        # then lay the (last `size`) roped keys into their ring slots
        # (token p -> slot p mod size), so subsequent decode steps see a
        # consistent ring buffer.
        size = cache["k"].shape[1]
        out = attention_ref(q, k, v, positions, positions, window,
                            logit_softcap)
        if s >= size:
            ck = jnp.roll(k[:, -size:].astype(cache["k"].dtype),
                          s % size, axis=1)
            cv = jnp.roll(v[:, -size:].astype(cache["v"].dtype),
                          s % size, axis=1)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
        new_cache = {"k": ck, "v": cv, "len": cache["len"] + s}
    elif cache is not None:
        # Decode: ring-buffer cache.  SWA layers allocate only ``window``
        # slots; slot j currently holds absolute position
        #   pos_j = idx - ((idx - j) mod size)
        # (negative => slot not yet written).  Keys are stored post-RoPE so
        # absolute positions are only needed for masking.
        idx = cache["len"]
        size = cache["k"].shape[1]
        slot = idx % size
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        j = jnp.arange(size, dtype=jnp.int32)
        kv_pos = idx - jnp.mod(idx - j, size)
        out = attention_ref(q, ck, cv, positions, kv_pos, window,
                            logit_softcap, kv_valid=kv_pos >= 0)
        new_cache = {"k": ck, "v": cv, "len": idx + s}
    else:
        kv_pos = positions
        if impl == "pallas":
            from repro.kernels.flash_attention import ops as fa_ops
            out = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                         logit_softcap=logit_softcap)
        elif impl == "blockwise":
            out = attention_blockwise(q, k, v, positions, kv_pos, window,
                                      logit_softcap)
        else:
            out = attention_ref(q, k, v, positions, kv_pos, window,
                                logit_softcap)
        new_cache = None

    y = einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU family)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    si, so = 1.0 / jnp.sqrt(d_model), 1.0 / jnp.sqrt(d_ff)
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * si).astype(dtype),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * si).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * so).astype(dtype),
    }


@tracing.scoped(tracing.FFN)
def gated_mlp(params: Params, x: jax.Array) -> jax.Array:
    gate = jax.nn.silu(matmul(x, params["w_gate"]))
    return matmul(gate * matmul(x, params["w_up"]), params["w_down"])
