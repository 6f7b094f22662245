"""SVR-INTERACT (Algorithm 2): variance-reduced INTERACT.

Identical consensus + tracking skeleton as Algorithm 1, but the local
gradients are SPIDER/SARAH-style recursive estimators refreshed with a
full-gradient pass every q iterations:

  mod(t, q) == 0:  p_t = grad_bar f(x_t, y_t)          (full, eqs. 8-9)
  otherwise:       p_t = p_{t-1} + (1/|S|) sum_xi [grad_bar f(x_t; xi)
                                 - grad_bar f(x_{t-1}; xi)]      (23)
                   d_t analogous for grad_y g                    (24)

with the K-term stochastic Neumann hypergradient of eq. (22) on minibatch
samples.  The paper sets |S| = q = ceil(sqrt(n)) which yields the
O(sqrt(n) eps^-1) sample complexity of Corollary 4.

Quickstart (the unified Solver API, see docs/SOLVERS.md)::

    from repro.solvers import SolverConfig, make_solver
    solver = make_solver(SolverConfig(algo="svr-interact", q=25))
    state = solver.init(None, problem, hg_cfg, x0, y0, data)
    state = solver.run(state, data, 100)   # scan-compiled

``make_svr_interact_step`` remains as a deprecated shim over that path.
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
from repro.hypergrad import HypergradConfig, local_gradients

__all__ = ["SvrState", "init_svr_state", "per_agent_keys",
           "svr_interact_step", "make_svr_interact_step"]


def per_agent_keys(key: jax.Array, m: int) -> jax.Array:
    """Agent i's sampling key as ``fold_in(key, i)`` — stacked (m, 2).

    Unlike ``jax.random.split(key, m)``, whose i-th output depends on m,
    ``fold_in`` keys depend only on the agent index: agent i draws the
    same stream whether the state carries m or a ghost-padded m' > m
    agents.  Every stochastic algorithm derives its per-agent keys here,
    which is what keeps active-agent trajectories bitwise invariant
    under the sweep engine's agent padding (docs/SWEEPS.md).
    """
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(m))


class SvrState(NamedTuple):
    x: object
    y: object
    u: object        # tracked gradient
    v: object        # inner-gradient estimator d_t
    p_prev: object   # previous outer estimator p_{t-1}
    x_prev: object   # previous iterates (needed by the recursive estimator)
    y_prev: object
    t: jax.Array
    key: jax.Array
    ef: object = None  # error-feedback residuals {"x", "u"} (compressed wire)
    guard: object = None  # divergence-guard counters {"tripped", "last_good"}


def _sample_batch(key, data_x, data_y, batch_size):
    idx = jax.random.randint(key, (batch_size,), 0, data_x.shape[0])
    return data_x[idx], data_y[idx]


def _full_grads(problem, hg_cfg, x, y, data: AgentData, key):
    inner_b = (data.inner_x, data.inner_y)
    outer_b = (data.outer_x, data.outer_y)
    p, v, _ = local_gradients(problem, hg_cfg, x, y, outer_b, inner_b, key)
    return p, v


def _minibatch_grads(problem, hg_cfg, x, y, data: AgentData, key, batch_size):
    k_in, k_out, k_neu = jax.random.split(key, 3)
    inner_b = _sample_batch(k_in, data.inner_x, data.inner_y, batch_size)
    outer_b = _sample_batch(k_out, data.outer_x, data.outer_y, batch_size)
    p, v, _ = local_gradients(problem, hg_cfg, x, y, outer_b, inner_b,
                              k_neu)
    return p, v


@tracing.scoped(tracing.INIT)
def init_svr_state(problem: BilevelProblem, hg_cfg: HypergradConfig,
                   x0, y0, data: AgentData, key: jax.Array,
                   compression=None, guard=None) -> SvrState:
    m = data.inner_x.shape[0]
    bcast = lambda tree: jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (m,) + leaf.shape), tree)
    x, y = bcast(x0), bcast(y0)
    # 2-way split + fold_in: the state key and every agent key are
    # independent of m, so ghost-padded inits replay the active agents'
    # streams exactly (see per_agent_keys).
    k_state, k_agents = jax.random.split(key)
    p, v = jax.vmap(partial(_full_grads, problem, hg_cfg))(
        x, y, data, per_agent_keys(k_agents, m))
    # copies: no two state leaves may alias one buffer (step donation)
    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)
    return SvrState(x=x, y=y, u=p, v=v, p_prev=copy(p), x_prev=copy(x),
                    y_prev=copy(y), t=jnp.zeros((), jnp.int32), key=k_state,
                    ef=init_ef(compression, x=x, u=p), guard=guard)


def svr_interact_step(
    problem: BilevelProblem,
    hg_cfg: HypergradConfig,
    engine,
    alpha: float,
    beta: float,
    q: int,
    batch_size: int,
    state: SvrState,
    data: AgentData,
) -> SvrState:
    """One SVR-INTERACT iteration (raw body over a built engine).

    Consensus Steps 1/3 run through the shared step-core; only Step 2
    (the SPIDER estimator, full refresh every q steps) differs from
    Algorithm 1.
    """
    bs = batch_size

    def _vr_grads(x, y, x_prev, y_prev, v_prev, p_prev, data, key):
        """Per-agent recursive estimators (23)-(24) at minibatch bs."""
        k1, k2 = jax.random.split(key)
        p_now, v_now = _minibatch_grads(problem, hg_cfg, x, y, data, k1, bs)
        # Same samples evaluated at the previous iterate: reuse the key so
        # xi is common to both terms (correlated difference, eq. 23-24).
        p_old, v_old = _minibatch_grads(problem, hg_cfg, x_prev, y_prev,
                                        data, k1, bs)
        p = jax.tree_util.tree_map(lambda a, b, c: a + b - c,
                                   p_prev, p_now, p_old)
        v = jax.tree_util.tree_map(lambda a, b, c: a + b - c,
                                   v_prev, v_now, v_old)
        return p, v

    m = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    key, k_step = jax.random.split(state.key)
    agent_keys = per_agent_keys(k_step, m)

    def grads_fn(x_new, y_new):
        # Step 2: full refresh every q steps, recursive otherwise.
        full_p, full_v = jax.vmap(partial(_full_grads, problem, hg_cfg))(
            x_new, y_new, data, agent_keys)
        vr_p, vr_v = jax.vmap(_vr_grads)(
            x_new, y_new, state.x, state.y, state.v, state.p_prev,
            data, agent_keys)
        refresh = (state.t + 1) % q == 0
        pick = lambda a, b: jax.tree_util.tree_map(
            lambda ai, bi: jnp.where(refresh, ai, bi), a, b)
        return pick(full_p, vr_p), pick(full_v, vr_v), None

    x_new, y_new, u_new, v_new, p_new, ef_new, _ = (
        consensus_descent_and_track(
            engine, state.x, state.y, state.u, state.v, state.p_prev,
            alpha, beta, grads_fn, t=state.t, ef=state.ef))

    return SvrState(x=x_new, y=y_new, u=u_new, v=v_new, p_prev=p_new,
                    x_prev=state.x, y_prev=state.y,
                    t=state.t + 1, key=key, ef=ef_new, guard=state.guard)


def make_svr_interact_step(
    problem: BilevelProblem,
    hg_cfg: HypergradConfig,
    mixing: MixingSpec,
    alpha: float,
    beta: float,
    q: int,
    batch_size: int | None = None,
    backend: str = "dense",
    **backend_opts,
):
    """Deprecated shim: use ``repro.solvers.make_solver`` instead.

    Returns the registry solver's jitted step closure (state donated),
    preserving the legacy signature.  batch_size defaults to q (|S| = q).
    """
    warnings.warn(
        "make_svr_interact_step is deprecated; use repro.solvers."
        "make_solver(SolverConfig(algo='svr-interact', ...))",
        DeprecationWarning, stacklevel=2)
    from repro.solvers import SolverConfig, make_solver
    cfg = SolverConfig(algo="svr-interact", alpha=alpha, beta=beta, q=q,
                       batch_size=batch_size, mixing=mixing,
                       backend=backend, backend_opts=backend_opts)
    return make_solver(cfg).build(problem, hg_cfg).step
