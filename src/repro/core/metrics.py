"""The paper's convergence metric (eqs. 2 / 11) and its ingredients.

    M_t = ||grad l(x_bar)||^2            (stationarity of the average)
        + (1/m) sum_i ||x_i - x_bar||^2  (consensus error)
        + ||y* - y||^2                   (inner error, aggregated)

Evaluating grad l(x_bar) = grad_bar f(x_bar, y*(x_bar)) requires the inner
optimum; we compute y*(x) by running the strongly-convex inner problem to
tolerance with gradient descent (exact up to solver precision — this is an
*evaluation-only* cost, not part of any algorithm's sample complexity).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.bilevel import AgentData, BilevelProblem
from repro.hypergrad import HypergradConfig, hypergradient

__all__ = ["MetricReport", "solve_inner", "convergence_metric",
           "convergence_metric_fn", "masked_convergence_metric",
           "masked_convergence_metric_fn"]


class MetricReport(NamedTuple):
    total: jax.Array
    stationarity: jax.Array
    consensus_error: jax.Array
    inner_error: jax.Array
    outer_loss: jax.Array


def _tree_sq_norm(tree) -> jax.Array:
    return sum(jnp.sum(jnp.square(l)) for l in jax.tree_util.tree_leaves(tree))


def _tree_mean_over_agents(tree):
    return jax.tree_util.tree_map(lambda l: jnp.mean(l, axis=0), tree)


def solve_inner(problem: BilevelProblem, x, y0, batch,
                steps: int = 400, lr: float = 0.5):
    """y*(x) via GD on the strongly-convex inner problem (single agent)."""
    grad_g = jax.grad(problem.inner, argnums=1)

    def body(_, y):
        g = grad_g(x, y, batch)
        return jax.tree_util.tree_map(lambda yi, gi: yi - lr * gi, y, g)

    return jax.lax.fori_loop(0, steps, body, y0)


def convergence_metric(problem: BilevelProblem, hg_cfg: HypergradConfig,
                       x_stack, y_stack, inner_steps: int, inner_lr: float,
                       data: AgentData) -> MetricReport:
    """Compute M_t for stacked per-agent iterates (leading axis m).

    Jitted (``problem``, ``hg_cfg``, ``inner_steps`` and ``inner_lr``
    static); the dispatch is the ``repro.eq11`` host span.
    """
    with tracing.host_span(tracing.EQ11):
        return _convergence_metric(problem, hg_cfg, x_stack, y_stack,
                                   inner_steps, inner_lr, data)


@partial(jax.jit, static_argnums=(0, 1, 4, 5))
@tracing.scoped(tracing.EQ11)
def _convergence_metric(problem: BilevelProblem, hg_cfg: HypergradConfig,
                        x_stack, y_stack, inner_steps: int, inner_lr: float,
                        data: AgentData) -> MetricReport:
    m = jax.tree_util.tree_leaves(x_stack)[0].shape[0]
    x_bar = _tree_mean_over_agents(x_stack)

    # --- consensus error: (1/m) sum_i ||x_i - x_bar||^2
    cons = jax.tree_util.tree_map(
        lambda xi, xb: jnp.sum(jnp.square(xi - xb[None])), x_stack, x_bar)
    consensus_error = sum(jax.tree_util.tree_leaves(cons)) / m

    # --- inner error: sum_i ||y_i*(x_i) - y_i||^2  at the *current* x_i
    inner_batches = (data.inner_x, data.inner_y)

    def agent_inner_err(x_i, y_i, batch):
        y_star = solve_inner(problem, x_i, y_i, batch, inner_steps, inner_lr)
        return _tree_sq_norm(jax.tree_util.tree_map(
            lambda a, b: a - b, y_star, y_i))

    inner_error = jnp.sum(jax.vmap(agent_inner_err)(
        x_stack, y_stack, inner_batches))

    # --- stationarity: ||grad l(x_bar)||^2 with y* at x_bar per agent.
    def agent_hypergrad_at_bar(y_i, inner_b, outer_b):
        y_star = solve_inner(problem, x_bar, y_i, inner_b,
                             inner_steps, inner_lr)
        p = hypergradient(problem.outer, problem.inner, x_bar, y_star,
                          hg_cfg, f_args=(outer_b,), g_args=(inner_b,),
                          inner_hess_yy=problem.inner_hess_yy)
        f_val = problem.outer(x_bar, y_star, outer_b)
        return p, f_val

    outer_batches = (data.outer_x, data.outer_y)
    p_all, f_all = jax.vmap(agent_hypergrad_at_bar)(
        y_stack, inner_batches, outer_batches)
    grad_l = _tree_mean_over_agents(p_all)
    stationarity = _tree_sq_norm(grad_l)
    outer_loss = jnp.mean(f_all)

    total = stationarity + consensus_error + inner_error
    return MetricReport(total=total, stationarity=stationarity,
                        consensus_error=consensus_error,
                        inner_error=inner_error, outer_loss=outer_loss)


# -- ghost-masked metric (the padded sweep engine's counterpart) -----------
#
# The padded sweep (docs/SWEEPS.md) batches experiments whose agent count
# differs by ghost-padding every state/data tensor to a common m_pad;
# ghost agents must not contribute to M_t.  Beyond masking, the agent
# reductions here are *association-stable*: a sequential fold over the
# agent axis, so the sum over the active agents is built in exactly the
# same float association whatever m_pad is.  (jnp.mean/jnp.sum may pick
# a different reduction tree for different array sizes, which would
# break the bitwise padded-vs-unpadded trace contract even though the
# ghost terms are exact zeros.)


def _masked_agent_sum(tree, num_active):
    """Sequential masked sum over the leading agent axis of every leaf."""
    m_pad = jax.tree_util.tree_leaves(tree)[0].shape[0]

    def body(i, acc):
        take = jax.tree_util.tree_map(
            lambda l: jnp.where(i < num_active, l[i], jnp.zeros_like(l[i])),
            tree)
        return jax.tree_util.tree_map(jnp.add, acc, take)

    zero = jax.tree_util.tree_map(lambda l: jnp.zeros_like(l[0]), tree)
    return jax.lax.fori_loop(0, m_pad, body, zero)


@tracing.scoped(tracing.EQ11)
def masked_convergence_metric(problem: BilevelProblem,
                              hg_cfg: HypergradConfig,
                              x_stack, y_stack, inner_steps: int,
                              inner_lr: float, data: AgentData,
                              num_active) -> MetricReport:
    """M_t over the first ``num_active`` agents of ghost-padded iterates.

    Semantics match ``convergence_metric`` with m = num_active: ghost
    rows (agent index >= num_active) are excluded from every average and
    sum.  ``num_active`` may be a traced scalar — the padded sweep
    engine vmaps it per experiment — while the padded agent count is
    static from the leaf shapes.  Per-agent work (inner solves,
    hypergradients) still runs on ghost rows (their padded data keeps it
    finite); only the cross-agent reductions mask, so the result is
    independent of whatever the ghosts drifted to.
    """
    x_bar_sum = _masked_agent_sum(x_stack, num_active)
    na = jnp.asarray(num_active,
                     jax.tree_util.tree_leaves(x_bar_sum)[0].dtype)
    x_bar = jax.tree_util.tree_map(lambda l: l / na, x_bar_sum)

    # --- consensus error: per-agent squared distances, masked sum / m
    def agent_cons(x_i):
        return _tree_sq_norm(jax.tree_util.tree_map(
            lambda a, b: a - b, x_i, x_bar))

    cons_vec = jax.vmap(agent_cons)(x_stack)
    consensus_error = _masked_agent_sum(cons_vec, num_active) / na

    # --- inner error: masked sum of per-agent ||y_i*(x_i) - y_i||^2
    inner_batches = (data.inner_x, data.inner_y)

    def agent_inner_err(x_i, y_i, batch):
        y_star = solve_inner(problem, x_i, y_i, batch, inner_steps, inner_lr)
        return _tree_sq_norm(jax.tree_util.tree_map(
            lambda a, b: a - b, y_star, y_i))

    inner_error = _masked_agent_sum(
        jax.vmap(agent_inner_err)(x_stack, y_stack, inner_batches),
        num_active)

    # --- stationarity: ||grad l(x_bar)||^2, the per-agent hypergradients
    # at x_bar averaged over active agents only.
    def agent_hypergrad_at_bar(y_i, inner_b, outer_b):
        y_star = solve_inner(problem, x_bar, y_i, inner_b,
                             inner_steps, inner_lr)
        p = hypergradient(problem.outer, problem.inner, x_bar, y_star,
                          hg_cfg, f_args=(outer_b,), g_args=(inner_b,),
                          inner_hess_yy=problem.inner_hess_yy)
        f_val = problem.outer(x_bar, y_star, outer_b)
        return p, f_val

    outer_batches = (data.outer_x, data.outer_y)
    p_all, f_all = jax.vmap(agent_hypergrad_at_bar)(
        y_stack, inner_batches, outer_batches)
    grad_l = jax.tree_util.tree_map(
        lambda l: l / na, _masked_agent_sum(p_all, num_active))
    stationarity = _tree_sq_norm(grad_l)
    outer_loss = _masked_agent_sum(f_all, num_active) / na

    total = stationarity + consensus_error + inner_error
    return MetricReport(total=total, stationarity=stationarity,
                        consensus_error=consensus_error,
                        inner_error=inner_error, outer_loss=outer_loss)


def masked_convergence_metric_fn(problem: BilevelProblem,
                                 hg_cfg: HypergradConfig,
                                 inner_steps: int = 300,
                                 inner_lr: float = 0.5):
    """Traceable ``(state, data, num_active) -> M_t`` for padded sweeps.

    Unlike ``convergence_metric_fn`` the data is an argument, not a
    closure constant: the padded sweep engine maps per-experiment padded
    datasets and active-agent counts as vmap operands.  Within one
    padded group, call it as ``lambda st: fn(st, data, num_active)``
    with the traced operands closed over (repro.solvers.sweep does).
    """

    def metric(state, data: AgentData, num_active):
        rep = masked_convergence_metric(problem, hg_cfg, state.x, state.y,
                                        inner_steps, inner_lr, data,
                                        num_active)
        return rep.total

    return metric


def convergence_metric_fn(problem: BilevelProblem, hg_cfg: HypergradConfig,
                          data: AgentData, inner_steps: int = 300,
                          inner_lr: float = 0.5):
    """A traceable ``state -> M_t`` closure for in-scan recording.

    ``convergence_metric`` itself is jitted and typically called eagerly
    (state in, Python float out) — that forces a host round-trip per
    record point.  The closure returned here stays abstract: it reads
    ``state.x`` / ``state.y`` and returns the scalar ``M_t`` as a traced
    value, so it can run inside ``lax.scan`` / ``lax.cond`` bodies
    (``Solver.run_traced``) and under ``jax.vmap`` (the sweep engine)
    while reusing the same hypergradient engine as the eager path —
    values are identical, only the dispatch boundary moves.

    The closure is a stable object: pass the *same* instance to repeated
    ``run_traced`` calls (it is a static jit argument there).
    """

    def metric(state):
        rep = convergence_metric(problem, hg_cfg, state.x, state.y,
                                 inner_steps, inner_lr, data)
        return rep.total

    return metric
