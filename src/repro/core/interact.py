"""The INTERACT algorithm (Algorithm 1).

Inner-gradient-descent-outer-tracked-gradient.  Per iteration each agent:

  Step 1 (consensus + descent):   x_i <- sum_j M_ij x_j - alpha u_i   (6)
                                  y_i <- y_i - beta v_i               (7)
  Step 2 (full local gradients):  p_i = grad_bar f_i(x_i, y_i)        (8)
                                  v_i = grad_y g_i(x_i, y_i)          (9)
  Step 3 (gradient tracking):     u_i <- sum_j M_ij u_j + p_i - p_i^- (10)

State tensors carry a leading agent dimension m; gradients are vmapped
per agent.  Steps 1 and 3 are delegated to a pluggable
``ConsensusEngine`` (repro/consensus) through the shared
``consensus_descent_and_track`` step-core — the same skeleton drives
SVR-INTERACT, the Section-6 baselines, and the distributed LM train step.
Step sizes must satisfy the Theorem-1 bounds, exposed by
``theorem1_step_sizes``.

Quickstart (the unified Solver API, see docs/SOLVERS.md)::

    from repro.solvers import SolverConfig, make_solver
    solver = make_solver(SolverConfig(algo="interact", alpha=0.3,
                                      backend="dense"))
    state = solver.init(None, problem, hg_cfg, x0, y0, data)
    state = solver.run(state, data, 100)   # scan-compiled multi-step

``backend`` selects the combine implementation: ``"dense"`` (matmul
reference), ``"pallas"`` (the fused consensus+tracking kernel on the
simulator hot loop), or ``"ppermute"`` (device-mesh collectives, used by
repro/train).  ``make_interact_step`` remains as a deprecated shim over
the solver path.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.consensus import as_engine, consensus_descent_and_track, init_ef
from repro.core.bilevel import AgentData, BilevelProblem
from repro.core.consensus import MixingSpec
from repro.hypergrad import HypergradConfig, local_gradients

__all__ = [
    "InteractState",
    "init_state",
    "interact_step",
    "make_interact_step",
    "theorem1_step_sizes",
]


class InteractState(NamedTuple):
    x: object        # outer params, leaves (m, ...)
    y: object        # inner params, leaves (m, ...)
    u: object        # tracked global gradient estimate, like x
    v: object        # inner gradient, like y
    p_prev: object   # previous local hypergradient, like x
    t: jax.Array     # iteration counter
    ef: object = None  # error-feedback residuals {"x", "u"} (compressed wire)
    guard: object = None  # divergence-guard counters {"tripped", "last_good"}


def _per_agent_batch(data: AgentData):
    inner = (data.inner_x, data.inner_y)
    outer = (data.outer_x, data.outer_y)
    return inner, outer


def _agent_gradients(problem: BilevelProblem, hg_cfg: HypergradConfig,
                     x, y, inner_batch, outer_batch, key=None):
    """(p_i, v_i) for a single agent (no leading agent dim here)."""
    p, v, _ = local_gradients(problem, hg_cfg, x, y, outer_batch,
                              inner_batch, key)
    return p, v


@tracing.scoped(tracing.INIT)
def init_state(problem: BilevelProblem, hg_cfg: HypergradConfig,
               x0, y0, data: AgentData,
               compression=None, guard=None) -> InteractState:
    """Algorithm-1 initialisation: u_0 = grad_bar f(x_0, y_0), v_0 = grad_y g.

    ``x0``/``y0`` are single-agent pytrees; every agent starts from the same
    point (x^0, y^0) as in the paper, so we broadcast along the agent axis.

    ``compression`` (a ``repro.consensus.CompressionConfig``) adds the
    zero error-feedback residuals for the two consensus streams to the
    state when it uses EF; otherwise ``ef`` stays ``None`` and the state
    is bit-identical to the uncompressed layout.  ``guard`` is the
    divergence-guard counter carry (``repro.byzantine.init_guard``), the
    same trailing-``None`` convention.
    """
    m = data.inner_x.shape[0]
    bcast = lambda tree: jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (m,) + leaf.shape), tree)
    x = bcast(x0)
    y = bcast(y0)
    inner_b, outer_b = _per_agent_batch(data)
    grads = jax.vmap(
        partial(_agent_gradients, problem, hg_cfg)
    )(x, y, inner_b, outer_b)
    p, v = grads
    # p_prev is a copy of p: u and p_prev must not alias the same buffer
    # or the donating step closures cannot donate the state.
    p_prev = jax.tree_util.tree_map(jnp.array, p)
    return InteractState(x=x, y=y, u=p, v=v, p_prev=p_prev,
                         t=jnp.zeros((), jnp.int32),
                         ef=init_ef(compression, x=x, u=p), guard=guard)


def interact_step(
    problem: BilevelProblem,
    hg_cfg: HypergradConfig,
    mixing,
    alpha: float,
    beta: float,
    state: InteractState,
    data: AgentData,
) -> InteractState:
    """One INTERACT iteration over all agents.

    ``mixing`` is a ``ConsensusEngine`` (or a raw (m, m) matrix, coerced
    to the dense backend).  Steps 1 and 3 run through the shared
    step-core; Step 2 is the full local gradient pass (8)-(9).
    """
    engine = as_engine(mixing)

    def grads_fn(x_new, y_new):
        inner_b, outer_b = _per_agent_batch(data)
        p_new, v_new = jax.vmap(
            partial(_agent_gradients, problem, hg_cfg)
        )(x_new, y_new, inner_b, outer_b)
        return p_new, v_new, None

    x_new, y_new, u_new, v_new, p_new, ef_new, _ = (
        consensus_descent_and_track(
            engine, state.x, state.y, state.u, state.v, state.p_prev,
            alpha, beta, grads_fn, t=state.t, ef=state.ef))

    return InteractState(x=x_new, y=y_new, u=u_new, v=v_new,
                         p_prev=p_new, t=state.t + 1, ef=ef_new,
                         guard=state.guard)


def make_interact_step(problem: BilevelProblem, hg_cfg: HypergradConfig,
                       mixing: MixingSpec, alpha: float, beta: float,
                       backend: str = "dense", **backend_opts):
    """Deprecated shim: use ``repro.solvers.make_solver`` instead.

    Returns the registry solver's jitted step closure (state donated),
    preserving the legacy positional signature.
    """
    warnings.warn(
        "make_interact_step is deprecated; use repro.solvers."
        "make_solver(SolverConfig(algo='interact', ...))",
        DeprecationWarning, stacklevel=2)
    from repro.solvers import SolverConfig, make_solver
    cfg = SolverConfig(algo="interact", alpha=alpha, beta=beta,
                       mixing=mixing, backend=backend,
                       backend_opts=backend_opts)
    return make_solver(cfg).build(problem, hg_cfg).step


def theorem1_step_sizes(
    mu_g: float,
    L_g: float,
    lam: float,
    m: int,
    L_f: float | None = None,
    safety: float = 1.0,
) -> tuple[float, float]:
    """Conservative (alpha, beta) satisfying the Theorem-1 bounds.

    The theorem lists ~10 upper bounds built from the Lipschitz constants of
    Lemma 1/2; we compute the binding ones from (mu_g, L_g, lam, m) with
    L_f defaulting to L_g.  ``safety`` < 1 shrinks both (useful when the
    constants are estimated rather than exact).
    """
    L_f = L_f if L_f is not None else L_g
    L_y = (L_g / mu_g) ** 2          # Lemma 1 with C_gxy ~ L_g
    L_l = (L_f + L_f * L_g / mu_g) ** 2
    L_K = max(L_f, L_g)

    beta = safety * min(
        3.0 * (mu_g + L_g) / (mu_g * L_g),
        1.0 / (mu_g + L_g),
    )
    r = beta * mu_g * L_g / (3.0 * (mu_g + L_g))
    one_minus = max(1.0 - lam, 1e-3)
    alpha = safety * min(
        1.0 / (4.0 * L_l),
        1.0 / (2.0 * m),
        1.0 / (m * one_minus),
        one_minus ** 2 / (32.0 * L_K ** 2),
        m * one_minus / (4.0 * L_l),
        9.0 * r * r * m * one_minus / (32.0 * L_y ** 2 * (1.0 + 1.0 / r) * L_f ** 2 + 1e-30),
        (1.0 - r) * (1.0 + r) * r * one_minus ** 2
        / (32.0 * L_y ** 2 * (mu_g + L_g) * L_K ** 2 * beta + 1e-30),
        one_minus / (4.0 * L_K),
        1.0,
    )
    return float(alpha), float(beta)
