"""Pallas kernel fusing the INTERACT consensus + tracking updates.

One iteration of the paper's core op (eqs. 6 and 10), fused so the agent
dimension stays VMEM-resident and x/u/p stream through once:

    x_out = M @ x - alpha * u            (consensus + descent)
    u_out = M @ u + p - p_prev           (gradient tracking)

Layout: parameters are flattened to (m, D); the grid tiles D.  The m x m
mixing matrix lives in VMEM for every tile, and both matmuls hit the MXU
with the (m, BD) tiles.  BD is picked from m (``pick_block_d``) so the
double-buffered tiles and the matrix fit the scoped VMEM the kernel asks
for.  Both matmuls run at HIGHEST precision: bf16-rounded mixing weights
break the exact average (``repro.core.consensus.mix_pytree``).  This is the single-host
m-agent simulator's hot loop; on the distributed runtime the same update is
expressed with ppermute (repro/sharding/collectives.py) instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

MAX_BLOCK_D = 512
LANE = 128
# Scoped VMEM the kernel requests (v5e: 128 MiB per core, 16 MiB scoped
# by default).
VMEM_LIMIT_BYTES = 64 * 2**20
# VMEM per (m, m) matrix entry: the f32 matrix double-buffered plus the
# bf16 pieces a HIGHEST-precision f32 matmul splits it into.  Mosaic on
# v5e allocated 20.4 bytes per entry (fit over m = 512, 768, 1024).
MATRIX_BYTES_PER_ENTRY = 24
PRECISION = jax.lax.Precision.HIGHEST


def pick_block_d(m: int, d: int, itemsize: int, streams: int) -> int:
    """Lane-tile width for ``streams`` double-buffered (m, BD) operands
    beside the (m, m) matrix, inside ``VMEM_LIMIT_BYTES``.

    The widest multiple of 128 up to ``MAX_BLOCK_D`` that fits; a D that
    fits in one such tile is one full-width block.  Raises, naming m,
    where not even a 128-lane tile fits.
    """
    fixed = MATRIX_BYTES_PER_ENTRY * m * m
    per_lane = 2 * streams * m * itemsize
    bd = MAX_BLOCK_D
    while bd >= LANE and fixed + per_lane * bd > VMEM_LIMIT_BYTES:
        bd -= LANE
    if bd < LANE:
        raise ValueError(
            f"consensus kernel: m={m} agents leave no room in "
            f"{VMEM_LIMIT_BYTES // 2**20} MiB of VMEM for even a {LANE}-lane "
            f"tile beside the (m, m) mixing matrix")
    return d if d <= bd else bd


def _consensus_kernel(mix_ref, x_ref, u_ref, p_ref, pprev_ref,
                      xout_ref, uout_ref, *, alpha: float):
    mix = mix_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    pp = pprev_ref[...].astype(jnp.float32)

    mx = jax.lax.dot_general(mix, x, (((1,), (0,)), ((), ())),
                             precision=PRECISION,
                             preferred_element_type=jnp.float32)
    mu = jax.lax.dot_general(mix, u, (((1,), (0,)), ((), ())),
                             precision=PRECISION,
                             preferred_element_type=jnp.float32)
    xout_ref[...] = (mx - alpha * u).astype(xout_ref.dtype)
    uout_ref[...] = (mu + p - pp).astype(uout_ref.dtype)


def _mix_kernel(mix_ref, x_ref, out_ref):
    mix = mix_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jax.lax.dot_general(
        mix, x, (((1,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def consensus_mix_kernel(
    mix: jax.Array,     # (m, m) doubly-stochastic
    x: jax.Array,       # (m, D)
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Bare combine ``M @ x`` — the mix-only half of the fused kernel,
    for callers that need eq. (6)/(10)'s combine without the tracking
    update (one matmul, two streams instead of five)."""
    m, d = x.shape
    bd = pick_block_d(m, max(d, 1), x.dtype.itemsize, streams=2)
    pad = (-d) % bd
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    dp = d + pad
    tile = pl.BlockSpec((m, bd), lambda i: (0, i))
    out = pl.pallas_call(
        _mix_kernel,
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((m, m), lambda i: (0, 0)), tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((m, dp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=resolve_interpret(interpret),
    )(mix, x)
    return out[:, :d] if pad else out


def consensus_step_kernel(
    mix: jax.Array,     # (m, m) doubly-stochastic
    x: jax.Array,       # (m, D) outer iterates
    u: jax.Array,       # (m, D) tracked gradients
    p: jax.Array,       # (m, D) new local hypergradients
    p_prev: jax.Array,  # (m, D)
    *,
    alpha: float,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    m, d = x.shape
    # Zero-pad D up to the tile multiple (real models rarely flatten to a
    # multiple of the tile); the pad lanes mix to zero and are sliced off.
    bd = pick_block_d(m, max(d, 1), x.dtype.itemsize, streams=6)
    pad = (-d) % bd
    if pad:
        padf = lambda t: jnp.pad(t, ((0, 0), (0, pad)))
        x, u, p, p_prev = padf(x), padf(u), padf(p), padf(p_prev)
    dp = d + pad
    grid = (dp // bd,)

    kernel = functools.partial(_consensus_kernel, alpha=alpha)
    tile = pl.BlockSpec((m, bd), lambda i: (0, i))

    x_out, u_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((m, m), lambda i: (0, 0)),
                  tile, tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((m, dp), x.dtype),
                   jax.ShapeDtypeStruct((m, dp), u.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=resolve_interpret(interpret),
    )(mix, x, u, p, p_prev)
    if pad:
        x_out, u_out = x_out[:, :d], u_out[:, :d]
    return x_out, u_out
