"""Neumann-series backends: the paper's eq. (22) estimator.

Deterministic form:   (1/L) sum_{j=0}^{K-1} (I - H/L)^j b
Stochastic form:      (K/L) (I - H/L)^k b,  k ~ U{0..K-1}

The stochastic chain runs with a *dynamic trip count* — ``fori_loop`` up
to the sampled ``k`` — so its expected cost is (K-1)/2 HVPs instead of
the seed's always-K masked loop (the masked form computed every HVP and
discarded the late ones).  The values are bit-identical: the executed
prefix of the product chain is the same op sequence.  Under ``vmap``
over agents the batched loop runs to the largest sampled ``k`` with
done lanes select-frozen, and each lane's counter reports its own k.

Backends registered here:

* ``neumann`` — the seed estimator over pytrees, HVP rebuilt per term
  (kept value-compatible as the reference).
* ``neumann-linearized`` — ``jax.linearize`` on ``grad_y g(x, .)`` once,
  the product chain replays the cached tangent in the flat raveled
  space, and the deterministic sum skips the seed's wasted K-th HVP
  (whose output was discarded), so it executes K-1 HVPs for the same
  value.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.hypergrad.config import HypergradConfig
from repro.hypergrad.engine import (HypergradEngine, hvp_yy,
                                    register_backend)
from repro.hypergrad.operator import (HypergradStats, LinearOperator,
                                      as_operator, tree_scale, tree_sub)

__all__ = [
    "neumann_truncated_apply",
    "neumann_stochastic_apply",
    "neumann_inverse_apply",
    "NeumannEngine",
    "NeumannLinearizedEngine",
]


def neumann_truncated_apply(matvec: Callable, b, k_terms: int,
                            lipschitz_g: float, *, skip_last: bool = False):
    """(1/L) sum_{j<K} (I - H/L)^j b, counting executed HVPs.

    Returns ``(value, hvp_count)``.  ``skip_last`` omits the K-th HVP
    whose output the truncated sum discards (K-1 HVPs, same value — used
    by the linearized backend); the default keeps the seed's executed-op
    sequence for bit-compatibility.
    """
    op = as_operator(matvec)
    L = lipschitz_g
    zero = jax.tree_util.tree_map(jnp.zeros_like, b)
    if k_terms <= 0:   # empty sum: match the reference loop exactly
        return zero, jnp.zeros((), jnp.int32)
    k_hvps = k_terms - 1 if skip_last else k_terms

    def body(_, carry):
        v, acc, count = carry
        acc = jax.tree_util.tree_map(jnp.add, acc, v)
        hv, count = op.apply_counted(v, count)
        v = tree_sub(v, tree_scale(1.0 / L, hv))
        return v, acc, count

    v, acc, count = jax.lax.fori_loop(
        0, k_hvps, body, (b, zero, jnp.zeros((), jnp.int32)))
    if skip_last:  # the final term joins the sum without a closing HVP
        acc = jax.tree_util.tree_map(jnp.add, acc, v)
    return tree_scale(1.0 / L, acc), count


def neumann_stochastic_apply(matvec: Callable, b, k_terms: int,
                             lipschitz_g: float, key: jax.Array):
    """(K/L) (I - H/L)^k b with k ~ U{0..K-1}, dynamic trip count.

    Returns ``(value, hvp_count)`` with ``hvp_count == k`` — the loop
    executes exactly the sampled number of HVPs (expected (K-1)/2)
    instead of masking out late terms of an always-K loop.
    """
    op = as_operator(matvec)
    L = lipschitz_g
    k = jax.random.randint(key, (), 0, k_terms)

    def body(_, carry):
        v, count = carry
        hv, count = op.apply_counted(v, count)
        return tree_sub(v, tree_scale(1.0 / L, hv)), count

    v, count = jax.lax.fori_loop(0, k, body,
                                 (b, jnp.zeros((), jnp.int32)))
    return tree_scale(float(k_terms) / L, v), count


def neumann_inverse_apply(
    g: Callable,
    x,
    y,
    b,
    *args,
    k_terms: int,
    lipschitz_g: float,
    stochastic_k: bool = False,
    key: jax.Array | None = None,
):
    """Approximate [H_yy g]^{-1} b with the Neumann series of eq. (22).

    Canonical successor of ``repro.core.hypergrad.neumann_inverse_apply``
    (same signature, bit-identical values; the stochastic path now costs
    the sampled k HVPs instead of always K).
    """
    matvec = lambda v: hvp_yy(g, x, y, v, *args)
    if stochastic_k:
        if key is None:
            raise ValueError("stochastic_k requires a PRNG key")
        v, _ = neumann_stochastic_apply(matvec, b, k_terms, lipschitz_g,
                                        key)
        return v
    v, _ = neumann_truncated_apply(matvec, b, k_terms, lipschitz_g)
    return v


@register_backend("neumann")
class NeumannEngine(HypergradEngine):
    """Seed eq.-(22) estimator: HVP rebuilt per term (the reference)."""

    def solve(self, g, x, y, b, cfg: HypergradConfig, g_args, key,
              inner_hess_yy=None):
        matvec = LinearOperator(lambda v: hvp_yy(g, x, y, v, *g_args))
        if cfg.stochastic_k:
            if key is None:
                raise ValueError("stochastic_k requires a PRNG key")
            z, count = neumann_stochastic_apply(
                matvec, b, cfg.neumann_k, cfg.lipschitz_g, key)
        else:
            z, count = neumann_truncated_apply(
                matvec, b, cfg.neumann_k, cfg.lipschitz_g)
        return z, HypergradStats.zero()._replace(hvp_count=count)


@register_backend("neumann-linearized")
class NeumannLinearizedEngine(HypergradEngine):
    """Linearize-once replay of the eq.-(22) product chain."""

    def solve(self, g, x, y, b, cfg: HypergradConfig, g_args, key,
              inner_hess_yy=None):
        grad_y = lambda yy: jax.grad(g, argnums=1)(x, yy, *g_args)
        _, hvp_lin = jax.linearize(grad_y, y)   # one grad_y g primal pass
        b_flat, unravel = ravel_pytree(b)
        op = LinearOperator(
            lambda vf: ravel_pytree(hvp_lin(unravel(vf)))[0])
        if cfg.stochastic_k:
            if key is None:
                raise ValueError("stochastic_k requires a PRNG key")
            z_flat, count = neumann_stochastic_apply(
                op, b_flat, cfg.neumann_k, cfg.lipschitz_g, key)
        else:
            z_flat, count = neumann_truncated_apply(
                op, b_flat, cfg.neumann_k, cfg.lipschitz_g,
                skip_last=True)
        stats = HypergradStats.zero()._replace(
            hvp_count=count, grad_count=jnp.int32(1))
        return unravel(z_flat), stats

    def local_gradients(self, problem, x, y, cfg: HypergradConfig,
                        f_batch, g_batch, key):
        """With a ``HeadSplit`` the deterministic chain runs on features
        computed once per call (``repro.hypergrad.head``)."""
        split = getattr(problem, "head", None)
        if split is None or cfg.stochastic_k:
            return super().local_gradients(problem, x, y, cfg, f_batch,
                                           g_batch, key)
        from repro.hypergrad.head import head_hypergradient_with_stats
        return head_hypergradient_with_stats(split, x, y, cfg,
                                             f_batch, g_batch)
