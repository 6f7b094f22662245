"""jit'd public wrapper for the WKV6 kernel (padding + initial state).

An incoming recurrent state (decode/chunked prefill) is folded in by
prepending nothing — the kernel starts from zero state — so ``wkv6``
handles it by running the kernel and then correcting the output with the
closed-form inter-segment term:

    o_t += (r_t * W_t) @ S_in,    S_out += diag(prod_t w_t) S_in

computed in plain jnp (cheap: one (seq, N) cumprod + one matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6.kernel import DEFAULT_CHUNK, wkv6_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, state: jax.Array | None = None,
         chunk: int = DEFAULT_CHUNK, interpret: bool | None = None
         ) -> tuple[jax.Array, jax.Array]:
    """WKV6 on (batch, seq, heads, N) tensors, as the model holds them;
    the kernel runs head-major, so inputs are transposed in and the
    output back out."""
    b, s, h, n = r.shape
    chunk = min(chunk, s) if s % min(chunk, s) == 0 else 1 if s == 1 else chunk
    pad = (-s) % chunk
    if pad:
        zeros = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r_, k_, v_ = zeros(r), zeros(k), zeros(v)
        # pad decays with 1.0 so the state is untouched by padded steps
        w_ = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                     constant_values=1.0)
    else:
        r_, k_, v_, w_ = r, k, v, w

    head_major = lambda t: jnp.swapaxes(t, 1, 2)
    out, s_final = wkv6_kernel(head_major(r_), head_major(k_), head_major(v_),
                               head_major(w_), u, chunk=chunk,
                               interpret=interpret)
    out = jnp.swapaxes(out, 1, 2)[:, :s]

    if state is not None:
        logw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))
        cum_excl = jnp.cumsum(logw, axis=1) - logw            # (b, s, h, n)
        r_decayed = r.astype(jnp.float32) * jnp.exp(cum_excl)
        extra = jnp.einsum("bshn,bhnm->bshm", r_decayed, state)
        out = (out.astype(jnp.float32) + extra).astype(r.dtype)
        total = jnp.sum(logw, axis=1)                         # (b, h, n)
        s_final = s_final + state * jnp.exp(total)[..., None]
    return out, s_final
