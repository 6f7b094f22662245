"""Pallas TPU kernel for the WKV6 recurrence (RWKV-6 "Finch").

Chunked formulation of the data-dependent-decay linear attention:

  S_t = diag(w_t) S_{t-1} + k_t v_t^T,   o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

A sequential scan serializes seq_len steps on the VPU; instead we split the
sequence into chunks of C tokens and compute per chunk (all MXU matmuls):

  inter-chunk:  o_t += (r_t * W_t) S_0            W_t = prod_{j<t} w_j
  intra-chunk:  o_t += sum_{s<t} [(r_t * W_t / W_{s+1}) . k_s] v_s
                       + (r_t * u . k_t) v_t      (bonus diagonal)
  state:        S_C = diag(W_C) S_0 + sum_s diag(W_C / W_{s+1}) k_s v_s

Decay products are kept in log space (w in (0,1) => log w < 0) so the
ratios W_t / W_{s+1} = exp(cum_t - cum_{s+1}) <= 1 never overflow.

Grid: (batch, heads, num_chunks) with the chunk dimension sequential
("arbitrary"), carrying the (N, N) state in VMEM scratch.  The kernel runs
on head-major (batch, heads, seq, N) tensors so every block's last two
dims are (chunk, N) — the head is a grid index, never a size-1 block dim,
which Mosaic's (8, 128) tiling refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_CHUNK = 64


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_final_ref,
                 state_ref, *, chunk: int, head_size: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, 0].astype(jnp.float32)         # (C, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)            # (1, N)

    logw = jnp.log(jnp.maximum(w, 1e-38))                    # (C, N) <= 0
    # Inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum); HIGHEST keeps it f32-exact on the MXU.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = (row >= col).astype(jnp.float32)
    cum = jax.lax.dot_general(lower, logw, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    cum_excl = cum - logw                                    # exclusive: sum_{j<t}

    state = state_ref[...]                                   # (N, N) k-major

    # ----- inter-chunk: o_t += (r_t * W_t) @ S0
    r_decayed = r * jnp.exp(cum_excl)
    o = jax.lax.dot_general(r_decayed, state, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # ----- intra-chunk: A[t, s] = sum_n r[t,n] k[s,n] exp(cum_excl[t]-cum[s])
    #                   (strictly lower triangular), bonus on the diagonal.
    # ratio exp(cum_excl[t] - cum[s]) <= 1 for s < t; clamp the masked upper
    # triangle before exp to avoid overflow there.  Accumulated one channel
    # n at a time on (C, C) tiles: a (C, C, N) broadcast needs shape casts
    # of the lane dim that Mosaic cannot lay out.
    tri = row > col
    k_t, cum_t = k.T, cum.T                                  # (N, C)
    A = jnp.zeros((chunk, chunk), jnp.float32)
    for n in range(head_size):
        diff = cum_excl[:, n:n + 1] - cum_t[n:n + 1, :]      # (C, C)
        ratio = jnp.exp(jnp.where(tri, diff, -1e30))         # 0 when masked
        A = A + r[:, n:n + 1] * k_t[n:n + 1, :] * ratio
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)        # (C, 1)
    A = A + jnp.where(row == col, bonus, 0.0)
    o = o + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    o_ref[0, 0] = o.astype(o_ref.dtype)

    # ----- state update: S = diag(exp(cum_C)) S0 + sum_s diag(exp(cum_C - cum_s)) k_s v_s
    total = cum[chunk - 1:chunk, :]                          # (1, N)
    k_scaled = k * jnp.exp(total - cum)                      # (C, N)
    # diag(exp(total)) @ S0 as a matmul: scales row i of the state.
    eye_n = (jax.lax.broadcasted_iota(jnp.int32, (head_size, head_size), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (head_size, head_size), 1))
    decay = jnp.where(eye_n, jnp.exp(total), 0.0)            # (N, N)
    state_ref[...] = jax.lax.dot_general(
        decay, state, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _emit_state():
        s_final_ref[0, 0, :, :] = state_ref[...]


def wkv6_kernel(
    r: jax.Array,  # (batch, heads, seq, N)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decays in (0, 1)
    u: jax.Array,  # (heads, N) bonus
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (b, h, s, N), final_state (b, h, N, N))."""
    b, h, s, n = r.shape
    assert s % chunk == 0, (s, chunk)
    grid = (b, h, s // chunk)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, head_size=n)
    io_spec = pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0))

    out, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            io_spec, io_spec, io_spec, io_spec,
            pl.BlockSpec((1, 1, n), lambda bi, hi, ci: (hi, 0, 0)),
        ],
        out_specs=[
            io_spec,
            pl.BlockSpec((1, 1, n, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, n), r.dtype),
            jax.ShapeDtypeStruct((b, h, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u[:, None])
    return out, s_final
