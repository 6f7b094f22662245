"""Distributed SVR-INTERACT (Algorithm 2) at LM scale.

Same consensus/tracking skeleton as ``repro/train/step.py`` but the local
gradients use the SPIDER-style recursive estimator (eqs. 23-24):

  mod(t, q) == 0:  p_t = local_grads(x_t, y_t)  on the full refresh batch
  otherwise:       p_t = p_{t-1} + grads(x_t, y_t; S) - grads(x_{t-1}, y_{t-1}; S)

with the *same* minibatch S evaluated at both iterates (the correlated
difference that makes the estimator variance-reduced).

Each ``local_grads`` call prices out as one eq.-(22) hypergradient —
K-1 head-space HVPs on the linearize-once tangent plus one backbone
cross term (see repro/hypergrad and docs/HYPERGRAD.md); the recursive
step pays it twice (new and previous iterate), matching the
``hypergrad_calls_per_step`` accounting of the simulator's SVR solver.

Cost note (documented design decision): the recursive estimator requires
the previous iterate (x_{t-1}, y_{t-1}) in state — two extra parameter
copies per agent on top of INTERACT's three.  At 100B+ scale that pushes
the per-chip state ~1.7x; the agents-per-pod layout (perf P6) absorbs it.
At LM scale the "full" refresh is approximated by a larger refresh batch
(the stream has no finite n); the paper's finite-sum refresh semantics
are preserved exactly in ``repro/core/svr_interact.py``.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.consensus import consensus_descent_and_track
from repro.launch.mesh import agent_axes
from repro.models.base import ArchConfig
from repro.train.bilevel_lm import local_grads
from repro.train.step import InteractConfig, TrainState, _agent_entry

__all__ = ["SvrTrainState", "init_svr_train_state", "make_svr_train_step"]


class SvrTrainState(NamedTuple):
    x: Any
    y: jax.Array
    u: Any
    v: jax.Array
    p_prev: Any
    x_prev: Any      # previous iterate (recursive estimator)
    y_prev: jax.Array
    t: jax.Array


def init_svr_train_state(cfg: ArchConfig, key: jax.Array,
                         m: int) -> SvrTrainState:
    from repro.train.step import init_train_state
    base: TrainState = init_train_state(cfg, key, m)
    return SvrTrainState(x=base.x, y=base.y, u=base.u, v=base.v,
                         p_prev=base.p_prev, x_prev=base.x,
                         y_prev=base.y, t=base.t)


def svr_train_state_specs(state_shapes: SvrTrainState, mesh,
                          agent_mode: str = "rows") -> SvrTrainState:
    from repro.train.step import train_state_specs
    base = train_state_specs(
        TrainState(x=state_shapes.x, y=state_shapes.y, u=state_shapes.u,
                   v=state_shapes.v, p_prev=state_shapes.p_prev,
                   t=state_shapes.t), mesh, agent_mode=agent_mode)
    return SvrTrainState(x=base.x, y=base.y, u=base.u, v=base.v,
                         p_prev=base.p_prev, x_prev=base.x,
                         y_prev=base.y, t=base.t)


def make_svr_train_step(cfg: ArchConfig, mesh, icfg: InteractConfig,
                        q: int | None = None, agent_mode: str = "rows"):
    """step(state, tokens) -> (state, metrics); refresh every q steps.

    ``icfg`` may be an ``InteractConfig`` or a unified
    ``repro.solvers.SolverConfig``; ``q=None`` reads the refresh period
    from the config (``InteractConfig.q`` / ``SolverConfig.q``).

    ``tokens``: (m, b, s) — the same batch plays the role of the refresh
    set on refresh steps and of S on recursive steps (deterministic
    streams make S fresh each call).
    """
    icfg = InteractConfig.coerce(icfg)
    if q is None:
        if icfg.q is None:
            raise ValueError("refresh period q not given and not set on "
                             "the config")
        q = icfg.q
    a_axes = ("pod",) if agent_mode == "pods" else agent_axes(mesh)
    aentry = _agent_entry(a_axes)
    hyper = icfg.hyper
    m = 1
    for ax in a_axes:
        m *= mesh.shape[ax]
    engine = icfg.consensus_engine(m, a_axes)

    def per_agent(state: SvrTrainState, tokens, ids):
        sq = lambda t: jax.tree_util.tree_map(lambda l: l[0], t)
        un = lambda t: jax.tree_util.tree_map(lambda l: l[None], t)

        refresh = (state.t + 1) % q == 0

        def grads_fn(x_new, y_new):
            toks = tokens[0]
            half = toks.shape[0] // 2
            inner_t, outer_t = toks[:half], toks[half:]

            # gradients at the new iterate (always needed)
            p_now, v_now, ce = local_grads(cfg, hyper, sq(x_new), y_new[0],
                                           inner_t, outer_t)
            # same minibatch at the previous iterate (recursive difference)
            p_old, v_old, _ = local_grads(cfg, hyper, sq(state.x_prev),
                                          state.y_prev[0], inner_t, outer_t)

            pick = lambda full, vr: jax.tree_util.tree_map(
                lambda a, b: jnp.where(refresh, a, b), full, vr)
            p_vr = jax.tree_util.tree_map(
                lambda pp, a, b: pp[0] + a - b, state.p_prev, p_now, p_old)
            v_vr = state.v[0] + v_now - v_old
            return un(pick(p_now, p_vr)), pick(v_now, v_vr)[None], ce

        x_new, y_new, u_new, v_new, p_new, _, ce = (
            consensus_descent_and_track(
                engine, state.x, state.y, state.u, state.v, state.p_prev,
                icfg.alpha, icfg.beta, grads_fn, t=state.t,
                agent_index=ids[0]))

        mean_ce = jax.lax.pmean(ce, aentry)
        new_state = SvrTrainState(
            x=x_new, y=y_new, u=u_new, v=v_new, p_prev=p_new,
            x_prev=state.x, y_prev=state.y, t=state.t + 1)
        return new_state, {"outer_ce": mean_ce,
                           "refresh": refresh.astype(jnp.float32)}

    def step(state: SvrTrainState, tokens):
        specs_state = jax.tree_util.tree_map(lambda _: P(aentry), state)
        specs_state = specs_state._replace(t=P())
        out_specs = (specs_state, {"outer_ce": P(), "refresh": P()})
        ids = jnp.arange(m, dtype=jnp.int32)
        fn = jax.shard_map(per_agent, mesh=mesh,
                           in_specs=(specs_state, P(aentry), P(aentry)),
                           out_specs=out_specs,
                           axis_names=set(a_axes), check_vma=False)
        return fn(state, tokens, ids)

    return step
