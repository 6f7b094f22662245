"""Baseline algorithms from Section 6: GT-DSGD and D-SGD.

* GT-DSGD — "stripped-down INTERACT": same consensus + gradient-tracking
  skeleton, but the local gradients are plain stochastic minibatch
  estimates (no variance reduction, no full refresh).
* D-SGD — GT-DSGD without gradient tracking: each agent descends its own
  stochastic hypergradient after the consensus combine.

Both use the stochastic Neumann hypergradient of eq. (22) for the outer
gradient (the bilevel analogue of a plain stochastic gradient).

Quickstart (the unified Solver API, see docs/SOLVERS.md)::

    from repro.solvers import SolverConfig, make_solver
    solver = make_solver(SolverConfig(algo="gt-dsgd", batch_size=12))
    state = solver.init(None, problem, hg_cfg, x0, y0, data)
    state = solver.run(state, data, 100)   # scan-compiled

``make_gt_dsgd_step`` / ``make_dsgd_step`` remain as deprecated shims.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.consensus import consensus_descent_and_track, init_ef
from repro.core.bilevel import AgentData, BilevelProblem
from repro.core.consensus import MixingSpec
from repro.hypergrad import HypergradConfig
from repro.core.svr_interact import _minibatch_grads, per_agent_keys

__all__ = [
    "GtDsgdState", "init_gt_dsgd_state", "gt_dsgd_step", "make_gt_dsgd_step",
    "DsgdState", "init_dsgd_state", "dsgd_step", "make_dsgd_step",
]


class GtDsgdState(NamedTuple):
    x: object
    y: object
    u: object
    v: object
    p_prev: object
    t: jax.Array
    key: jax.Array
    ef: object = None  # error-feedback residuals {"x", "u"} (compressed wire)
    guard: object = None  # divergence-guard counters {"tripped", "last_good"}


def _bcast(tree, m):
    return jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (m,) + leaf.shape), tree)


@tracing.scoped(tracing.INIT)
def init_gt_dsgd_state(problem: BilevelProblem, hg_cfg: HypergradConfig,
                       x0, y0, data: AgentData, key: jax.Array,
                       batch_size: int, compression=None,
                       guard=None) -> GtDsgdState:
    m = data.inner_x.shape[0]
    x, y = _bcast(x0, m), _bcast(y0, m)
    # m-independent key derivation (see per_agent_keys): ghost-padded
    # inits replay the active agents' sampling streams exactly.
    k_state, k_agents = jax.random.split(key)
    p, v = jax.vmap(
        partial(_minibatch_grads, problem, hg_cfg,
                batch_size=batch_size))(x, y, data,
                                        per_agent_keys(k_agents, m))
    # p_prev copied: u/p_prev must not alias one buffer (step donation)
    p_prev = jax.tree_util.tree_map(jnp.array, p)
    return GtDsgdState(x=x, y=y, u=p, v=v, p_prev=p_prev,
                       t=jnp.zeros((), jnp.int32), key=k_state,
                       ef=init_ef(compression, x=x, u=p), guard=guard)


def gt_dsgd_step(problem: BilevelProblem, hg_cfg: HypergradConfig,
                 engine, alpha: float, beta: float, batch_size: int,
                 state: GtDsgdState, data: AgentData) -> GtDsgdState:
    """One GT-DSGD iteration (raw body over a built engine)."""
    m = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    key, k_step = jax.random.split(state.key)
    agent_keys = per_agent_keys(k_step, m)

    def grads_fn(x_new, y_new):
        p_new, v_new = jax.vmap(
            partial(_minibatch_grads, problem, hg_cfg,
                    batch_size=batch_size))(x_new, y_new, data,
                                            agent_keys)
        return p_new, v_new, None

    x_new, y_new, u_new, v_new, p_new, ef_new, _ = (
        consensus_descent_and_track(
            engine, state.x, state.y, state.u, state.v, state.p_prev,
            alpha, beta, grads_fn, t=state.t, ef=state.ef))
    return GtDsgdState(x=x_new, y=y_new, u=u_new, v=v_new, p_prev=p_new,
                       t=state.t + 1, key=key, ef=ef_new,
                       guard=state.guard)


def make_gt_dsgd_step(problem: BilevelProblem, hg_cfg: HypergradConfig,
                      mixing: MixingSpec, alpha: float, beta: float,
                      batch_size: int, backend: str = "dense",
                      **backend_opts):
    """Deprecated shim: use ``repro.solvers.make_solver`` instead."""
    warnings.warn(
        "make_gt_dsgd_step is deprecated; use repro.solvers."
        "make_solver(SolverConfig(algo='gt-dsgd', ...))",
        DeprecationWarning, stacklevel=2)
    from repro.solvers import SolverConfig, make_solver
    cfg = SolverConfig(algo="gt-dsgd", alpha=alpha, beta=beta,
                       batch_size=batch_size, mixing=mixing,
                       backend=backend, backend_opts=backend_opts)
    return make_solver(cfg).build(problem, hg_cfg).step


class DsgdState(NamedTuple):
    x: object
    y: object
    t: jax.Array
    key: jax.Array
    ef: object = None  # error-feedback residual {"x"} (compressed wire)
    guard: object = None  # divergence-guard counters {"tripped", "last_good"}


@tracing.scoped(tracing.INIT)
def init_dsgd_state(x0, y0, m: int, key: jax.Array,
                    compression=None, guard=None) -> DsgdState:
    x = _bcast(x0, m)
    return DsgdState(x=x, y=_bcast(y0, m),
                     t=jnp.zeros((), jnp.int32), key=key,
                     ef=init_ef(compression, x=x), guard=guard)


def dsgd_step(problem: BilevelProblem, hg_cfg: HypergradConfig,
              engine, alpha: float, beta: float, batch_size: int,
              state: DsgdState, data: AgentData) -> DsgdState:
    """One D-SGD iteration (raw body over a built engine)."""
    m = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    key, k_step = jax.random.split(state.key)
    agent_keys = per_agent_keys(k_step, m)

    p, v = jax.vmap(
        partial(_minibatch_grads, problem, hg_cfg,
                batch_size=batch_size))(state.x, state.y, data, agent_keys)

    # No tracking: descend the raw stochastic hypergradient after the
    # consensus combine (D-SGD's single mix goes through the wire path —
    # compression / interval — when the engine has one configured).
    matrix = (engine.topology_matrix(state.t, state.x)
              if hasattr(engine, "topology_matrix") else None)
    if state.ef is not None or getattr(engine, "wire_active", False):
        ef_x = None if state.ef is None else state.ef.get("x")
        with tracing.scope(tracing.MIX):
            x_mixed, ef_x_new = engine.mix_ef(state.x, ef_x, state.t,
                                              matrix=matrix)
        ef_new = None if state.ef is None else {"x": ef_x_new}
    else:
        # mix_ef with no wire state is bitwise ``mix`` — routed through it
        # so an attached CommsLedger records D-SGD's single x stream too.
        with tracing.scope(tracing.MIX):
            x_mixed, _ = engine.mix_ef(state.x, None, state.t,
                                       matrix=matrix)
        ef_new = state.ef
    x_new = jax.tree_util.tree_map(
        lambda mx, g: mx - alpha * g, x_mixed, p)
    y_new = jax.tree_util.tree_map(
        lambda y, g: y - beta * g, state.y, v)
    return DsgdState(x=x_new, y=y_new, t=state.t + 1, key=key, ef=ef_new,
                     guard=state.guard)


def make_dsgd_step(problem: BilevelProblem, hg_cfg: HypergradConfig,
                   mixing: MixingSpec, alpha: float, beta: float,
                   batch_size: int, backend: str = "dense",
                   **backend_opts):
    """Deprecated shim: use ``repro.solvers.make_solver`` instead."""
    warnings.warn(
        "make_dsgd_step is deprecated; use repro.solvers."
        "make_solver(SolverConfig(algo='d-sgd', ...))",
        DeprecationWarning, stacklevel=2)
    from repro.solvers import SolverConfig, make_solver
    cfg = SolverConfig(algo="d-sgd", alpha=alpha, beta=beta,
                       batch_size=batch_size, mixing=mixing,
                       backend=backend, backend_opts=backend_opts)
    return make_solver(cfg).build(problem, hg_cfg).step
