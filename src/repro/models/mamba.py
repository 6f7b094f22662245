"""Mamba (S6) block — the SSM mixer of jamba's Mamba layers.

Selective state-space model, as HF Jamba's ``JambaMambaMixer`` writes it:

    (u, z)     = in_proj(x)                      u, z: (..., d_inner)
    u          = silu(causal_conv1d(u) + conv_b)
    (r, B, C)  = x_proj(u)                        r: dt_rank, B, C: d_state
    r, B, C    = RMSNorm(r), RMSNorm(B), RMSNorm(C)   (dt/b/c_layernorm)
    dt         = softplus(dt_proj(r) + dt_bias)   (..., d_inner)
    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t u_t  (diagonal A = -exp(a_log))
    y_t = C_t . h_t + D u_t
    out = out_proj(y * silu(z))

Training path uses ``jax.lax.associative_scan`` over time (parallel prefix
— the TPU-friendly formulation; a sequential scan would serialize 4k
steps).  Decode path is the O(1) single-step recurrence on a carried
state, which is what makes jamba eligible for long_500k.

Under tensor parallelism a rank holds ``d_inner / tp`` channels: its
in_proj columns, conv, dt_proj outputs, A, D and the matching rows of
x_proj and out_proj.  x_proj and out_proj then give partial sums; the
block is split at x_proj (``mamba_xproj`` / ``mamba_from_xproj``) so a
test can hand the channel half the summed projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models import layers as L

__all__ = ["init_mamba", "mamba_block", "mamba_xproj", "mamba_from_xproj",
           "mamba_prefill", "mamba_decode_step", "init_mamba_state"]


def init_mamba(key, d_model: int, d_state: int, d_conv: int,
               d_inner: int, dt_rank: int, dtype) -> dict:
    """One rank's mixer: ``d_inner`` channels held (all of them at tp 1)."""
    keys = jax.random.split(key, 5)
    si = 1.0 / jnp.sqrt(d_model)
    sinner = 1.0 / jnp.sqrt(d_inner)
    # S4D-real initialisation for A.
    a_init = jnp.tile(jnp.arange(1, d_state + 1, dtype=jnp.float32)[None, :],
                      (d_inner, 1))
    return {
        "w_in": (jax.random.normal(keys[0], (d_model, 2 * d_inner)) * si).astype(dtype),
        "conv_w": (jax.random.normal(keys[1], (d_conv, d_inner)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype=dtype),
        "w_x": (jax.random.normal(keys[2], (d_inner, dt_rank + 2 * d_state))
                * sinner).astype(dtype),
        "dt_norm": L.init_rms_norm(dt_rank, dtype),
        "b_norm": L.init_rms_norm(d_state, dtype),
        "c_norm": L.init_rms_norm(d_state, dtype),
        "w_dt": (jax.random.normal(keys[3], (dt_rank, d_inner))
                 / jnp.sqrt(dt_rank)).astype(dtype),
        "dt_bias": jnp.full((d_inner,), -4.0, dtype=dtype),  # softplus^-1(~0.018)
        "a_log": jnp.log(a_init).astype(dtype),
        "d_skip": jnp.ones((d_inner,), dtype=dtype),
        "w_out": (jax.random.normal(keys[4], (d_inner, d_model)) * sinner).astype(dtype),
    }


def _ssm_params(params, xproj, eps: float):
    """(dt, B, C, A) from the x_proj output (dt_rank + 2 d_state)."""
    d_state = params["b_norm"]["scale"].shape[0]
    dt_rank = xproj.shape[-1] - 2 * d_state
    dt_r, B, C = jnp.split(xproj, [dt_rank, dt_rank + d_state], axis=-1)
    dt_r = L.rms_norm(params["dt_norm"], dt_r, eps)
    B = L.rms_norm(params["b_norm"], B, eps)
    C = L.rms_norm(params["c_norm"], C, eps)
    dt = jax.nn.softplus(L.matmul(dt_r, params["w_dt"]) + params["dt_bias"])
    A = -jnp.exp(params["a_log"].astype(jnp.float32))  # (d_inner, d_state)
    return dt, B, C, A


def _causal_conv(params, x):
    """Depthwise causal conv1d over (batch, seq, d_inner)."""
    d_conv = params["conv_w"].shape[0]
    pad = jnp.pad(x, ((0, 0), (d_conv - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * params["conv_w"][i]
              for i in range(d_conv))
    return out + params["conv_b"]


def _ssm_apply(params, u, dt, B, C, A, h0=None):
    """Selective scan over the full given span; returns (y, h_last).

    h_t = decay_t h_{t-1} + drive_t, with optional incoming state h0
    folded in closed form: h_t += (prod_{j<=t} decay_j) h0.
    """
    dt32, u32 = dt.astype(jnp.float32), u.astype(jnp.float32)
    B32, C32 = B.astype(jnp.float32), C.astype(jnp.float32)
    log_decay = dt32[..., None] * A                   # (b, s, d_inner, N)
    decay = jnp.exp(log_decay)
    drive = (dt32 * u32)[..., None] * B32[..., None, :]

    def combine(a, b_):
        d1, x1 = a
        d2, x2 = b_
        return d1 * d2, x1 * d2 + x2

    _, h = jax.lax.associative_scan(combine, (decay, drive), axis=1)
    if h0 is not None:
        h = h + jnp.exp(jnp.cumsum(log_decay, axis=1)) * h0[:, None]
    y = L.einsum("bsdn,bsn->bsd", h, C32)
    y = y + params["d_skip"].astype(jnp.float32) * u32
    return y, h[:, -1]


def mamba_xproj(params: dict, x: jax.Array):
    """In_proj, conv and x_proj: ``(u, z, xproj)``, xproj a partial sum
    over the channels this rank holds."""
    xz = L.matmul(x, params["w_in"])
    u, z = jnp.split(xz, 2, axis=-1)                  # (b, s, d_inner)
    u = jax.nn.silu(_causal_conv(params, u))
    return u, z, L.matmul(u, params["w_x"])


def mamba_from_xproj(params: dict, u, z, xproj, out_dtype,
                     seq_chunk: int | None = None, eps: float = 1e-6):
    """dt/B/C norms, the selective scan, the D skip, the gate, out_proj."""
    b, s, _ = u.shape
    dt, B, C, A = _ssm_params(params, xproj, eps)

    if seq_chunk is None or s % seq_chunk != 0 or s <= seq_chunk:
        y, _ = _ssm_apply(params, u, dt, B, C, A)
    else:
        nc = s // seq_chunk
        resh = lambda t: jnp.moveaxis(
            t.reshape(b, nc, seq_chunk, *t.shape[2:]), 1, 0)
        d_inner = u.shape[-1]
        h0 = jnp.zeros((b, d_inner, A.shape[-1]), jnp.float32)

        def body(h, xs):
            uc, dtc, Bc, Cc = xs
            yc, h_new = _ssm_apply(params, uc, dtc, Bc, Cc, A, h0=h)
            return h_new, yc

        _, ys = jax.lax.scan(jax.checkpoint(body), h0,
                             (resh(u), resh(dt), resh(B), resh(C)))
        y = jnp.moveaxis(ys, 0, 1).reshape(b, s, -1)

    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(out_dtype)
    return L.matmul(y, params["w_out"])


@tracing.scoped(tracing.MAMBA)
def mamba_block(params: dict, x: jax.Array,
                seq_chunk: int | None = None,
                eps: float = 1e-6) -> jax.Array:
    """x: (batch, seq, d_model) -> same; training/prefill path.

    ``seq_chunk`` (perf P7): run the selective scan in sequence chunks
    with a carried (d_inner, N) state — bounds the (b, s, d_inner, N)
    decay/drive temporaries to O(b * chunk * d_inner * N).  This is the
    XLA-side analogue of the fused mamba kernel's working-set control.
    """
    u, z, xproj = mamba_xproj(params, x)
    return mamba_from_xproj(params, u, z, xproj, x.dtype, seq_chunk, eps)


def mamba_prefill(params: dict, x: jax.Array,
                  eps: float = 1e-6) -> tuple[jax.Array, dict]:
    """Full-sequence forward that also emits the decode state (fresh
    cache): recurrent h after the last token + the conv tail."""
    b, s, _ = x.shape
    xz = L.matmul(x, params["w_in"])
    u_pre, z = jnp.split(xz, 2, axis=-1)
    u = jax.nn.silu(_causal_conv(params, u_pre))
    dt, B, C, A = _ssm_params(params, L.matmul(u, params["w_x"]), eps)
    y, h_last = _ssm_apply(params, u, dt, B, C, A)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = L.matmul(y, params["w_out"])

    d_conv = params["conv_w"].shape[0]
    tail = d_conv - 1
    if s >= tail:
        conv_tail = u_pre[:, s - tail:, :]
    else:
        conv_tail = jnp.pad(u_pre, ((0, 0), (tail - s, 0), (0, 0)))
    return out, {"h": h_last, "conv": conv_tail.astype(x.dtype)}


def init_mamba_state(batch: int, d_inner: int, d_state: int, d_conv: int,
                     dtype) -> dict:
    return {
        "h": jnp.zeros((batch, d_inner, d_state), dtype=jnp.float32),
        "conv": jnp.zeros((batch, d_conv - 1, d_inner), dtype=dtype),
    }


def mamba_decode_step(params: dict, x: jax.Array, state: dict,
                      eps: float = 1e-6) -> tuple[jax.Array, dict]:
    """Single-token step.  x: (batch, 1, d_model)."""
    xz = L.matmul(x, params["w_in"])
    u, z = jnp.split(xz, 2, axis=-1)                  # (b, 1, d_inner)
    conv_buf = jnp.concatenate([state["conv"], u.astype(state["conv"].dtype)], axis=1)
    u_conv = jnp.einsum("bkd,kd->bd", conv_buf, params["conv_w"]) + params["conv_b"]
    u_act = jax.nn.silu(u_conv)[:, None, :]           # (b, 1, d_inner)

    dt, B, C, A = _ssm_params(params, L.matmul(u_act, params["w_x"]), eps)
    dt32 = dt[:, 0].astype(jnp.float32)
    decay = jnp.exp(dt32[..., None] * A)              # (b, d_inner, d_state)
    drive = (dt32 * u_act[:, 0].astype(jnp.float32))[..., None] * \
        B[:, 0].astype(jnp.float32)[:, None, :]
    h = state["h"] * decay + drive
    y = jnp.einsum("bdn,bn->bd", h, C[:, 0].astype(jnp.float32))
    y = y + params["d_skip"].astype(jnp.float32) * u_act[:, 0].astype(jnp.float32)
    y = (y * jax.nn.silu(z[:, 0].astype(jnp.float32))).astype(x.dtype)
    out = L.matmul(y, params["w_out"])[:, None, :]
    return out, {"h": h, "conv": conv_buf[:, 1:, :]}
