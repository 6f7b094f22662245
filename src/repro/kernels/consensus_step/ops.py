"""jit'd wrapper: apply the fused consensus step to a whole pytree.

Ravels every agent's subtree to a flat (m, D) matrix via
``jax.flatten_util.ravel_pytree`` (vmapped over the leading agent dim),
runs the kernel once over the concatenated parameter vector — the kernel
itself zero-pads D up to the tile size — and unravels back.  This is the
implementation layer of the ``pallas`` consensus backend
(``repro/consensus/pallas.py``).
"""
from __future__ import annotations

import functools

import jax
from jax.flatten_util import ravel_pytree

from repro.kernels.consensus_step.kernel import (
    consensus_mix_kernel, consensus_step_kernel)

__all__ = ["consensus_mix", "consensus_step", "flatten_agents",
           "unflatten_agents"]


def flatten_agents(tree):
    """(m, ...)-leaved pytree -> ((m, D) matrix, per-agent unravel fn)."""
    one_agent = jax.tree_util.tree_map(lambda l: l[0], tree)
    _, unravel = ravel_pytree(one_agent)
    flat = jax.vmap(lambda t: ravel_pytree(t)[0])(tree)
    return flat, unravel


def unflatten_agents(flat, unravel):
    return jax.vmap(unravel)(flat)


@functools.partial(jax.jit, static_argnames=("interpret",))
def consensus_mix(mix: jax.Array, tree, *, interpret: bool | None = None):
    """Bare combine ``x_i <- sum_j M_ij x_j`` over a pytree (one matmul)."""
    X, unravel = flatten_agents(tree)
    X_out = consensus_mix_kernel(mix, X, interpret=interpret)
    return unflatten_agents(X_out, unravel)


@functools.partial(jax.jit, static_argnames=("alpha", "interpret"))
def consensus_step(mix: jax.Array, x_tree, u_tree, p_tree, pprev_tree, *,
                   alpha: float, interpret: bool | None = None):
    """Returns (x_tree', u_tree') after one fused eq.(6)+(10) update."""
    X, unravel_x = flatten_agents(x_tree)
    # u gets its own unravel: for mixed-dtype trees, x's unravel would
    # silently cast the tracker to x's leaf dtypes on the way back.
    U, unravel_u = flatten_agents(u_tree)
    P, _ = flatten_agents(p_tree)
    PP, _ = flatten_agents(pprev_tree)

    X_out, U_out = consensus_step_kernel(mix, X, U, P, PP, alpha=alpha,
                                         interpret=interpret)
    return (unflatten_agents(X_out, unravel_x),
            unflatten_agents(U_out, unravel_u))
