"""Distributed INTERACT train step (shard_map + pjit hybrid).

Layout (DESIGN.md §5): the paper's m agents are the rows of the agent axes
(("data",) single-pod, ("pod", "data") multi-pod).  Every per-agent tensor
carries a leading agent dim of size m sharded one-agent-per-row, so the
per-device footprint equals plain data-parallel training while each agent
keeps a *distinct* x_i — exactly Problem (1).

The step body runs under ``shard_map`` over the agent axes only; the
``model`` axis stays auto, so XLA partitions every einsum in the backbone
exactly as in the serving path.  Consensus (eqs. 6/10) goes through the
``ConsensusEngine`` selected by ``InteractConfig`` — by default the
``ppermute`` backend, which decomposes the configured topology's mixing
matrix (ring, Erdős–Rényi, or torus — see ``InteractConfig.topology``)
into per-offset neighbour exchanges, so the paper-faithful ER-graph
Section-6 scenario runs on the distributed runtime, not just the ICI
ring.  int8 wire compression and local-DP noise are engine options.

One call == one INTERACT iteration (Algorithm 1), expressed through the
shared ``consensus_descent_and_track`` step-core (repro/consensus):
  Step 1: x <- mix(x) - alpha*u ; y <- y - beta*v
  Step 2: (p, v) local hypergradient / inner gradient at the new iterate
  Step 3: u <- mix(u) + p - p_prev
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.consensus import consensus_descent_and_track, make_engine
from repro.core.consensus import MixingSpec
from repro.launch.mesh import agent_axes, agent_count
from repro.models import model as M
from repro.models.base import ArchConfig
from repro.sharding.partition import (
    leaf_spec, stacked_tree_specs, tree_shardings)
from repro.train.bilevel_lm import BilevelHyper, local_grads

__all__ = ["TrainState", "InteractConfig", "init_train_state",
           "train_state_specs", "make_train_step", "make_eval_step"]


class TrainState(NamedTuple):
    x: Any            # backbone params, leaves (m, ...)
    y: jax.Array      # per-agent heads (m, d_model, vocab)
    u: Any            # tracked gradient, like x
    v: jax.Array      # inner gradient, like y
    p_prev: Any       # previous hypergradient, like x
    t: jax.Array      # step counter (replicated)


@dataclasses.dataclass(frozen=True)
class InteractConfig:
    alpha: float = 1e-2          # outer step size (Theorem 1 bound applies)
    beta: float = 0.5            # inner step size
    self_weight: float = 1.0 / 3.0  # ring mixing w0; lambda analytic
    hyper: BilevelHyper = BilevelHyper()
    # consensus engine selection (repro/consensus):
    consensus_backend: str = "ppermute"    # only mesh-native backend today
    topology: str = "ring"                 # ring | erdos-renyi | torus
    p_connect: float = 0.5                 # ER edge probability
    topology_seed: int = 0                 # ER graph sample seed
    # paper future-work extensions (conclusion, both opt-in):
    consensus_compress: str | None = None  # "int8" compressed consensus
    dp_sigma: float = 0.0                  # local-DP noise on shared x
    # SVR refresh period (used by make_svr_train_step when q not given)
    q: int | None = None

    def topology_config(self):
        """The declarative graph shared with ``repro.solvers``."""
        from repro.solvers.config import TopologyConfig
        return TopologyConfig(kind=self.topology, p_connect=self.p_connect,
                              seed=self.topology_seed,
                              self_weight=self.self_weight)

    def mixing_spec(self, m: int) -> MixingSpec:
        """The configured topology's mixing matrix for m agents."""
        return self.topology_config().mixing_spec(m)

    def solver_config(self, algo: str = "interact"):
        """The equivalent unified ``SolverConfig`` (docs/SOLVERS.md).

        The LM path's hypergradient is the head-space Neumann series on
        cached features — the linearize-once replay of eq. (22) — so the
        exported ``HypergradConfig`` records it as the
        ``neumann-linearized`` backend with BilevelHyper's K and L_g
        (round-tripped back by ``from_solver_config``).
        """
        from repro.hypergrad import HypergradConfig
        from repro.solvers.config import SolverConfig
        opts = {}
        if self.consensus_compress is not None:
            opts["compress"] = self.consensus_compress
        if self.dp_sigma:
            opts["dp_sigma"] = self.dp_sigma
        hg = HypergradConfig(method="neumann", backend="neumann-linearized",
                             neumann_k=self.hyper.neumann_k,
                             lipschitz_g=self.hyper.lipschitz_g)
        return SolverConfig(algo=algo, alpha=self.alpha, beta=self.beta,
                            q=self.q, topology=self.topology_config(),
                            backend=self.consensus_backend,
                            backend_opts=opts, hypergrad=hg)

    @classmethod
    def from_solver_config(cls, scfg, hyper: BilevelHyper | None = None):
        """Build the LM-runtime config from a unified ``SolverConfig``.

        ``hyper`` (the LM-specific ``BilevelHyper``) defaults to
        ``BilevelHyper()``, with the Neumann settings (K, L_g) imported
        from ``scfg.hypergrad`` when it selects a Neumann estimator —
        the only eq.-(22) knobs with an LM counterpart.  ``scfg.seed``
        plays no role on the LM path (deterministic token streams).
        """
        if scfg.mixing is not None:
            raise ValueError(
                "SolverConfig.mixing (an explicit MixingSpec) cannot drive "
                "the distributed runtime — the mesh realises the graph from "
                "the declarative topology; set SolverConfig.topology instead")
        opts = dict(scfg.backend_opts)
        if hyper is None:
            hyper = BilevelHyper()
            if scfg.hypergrad.resolve_backend().startswith("neumann"):
                hyper = dataclasses.replace(
                    hyper, neumann_k=scfg.hypergrad.neumann_k,
                    lipschitz_g=scfg.hypergrad.lipschitz_g)
        return cls(alpha=scfg.alpha, beta=scfg.beta,
                   self_weight=scfg.topology.self_weight,
                   hyper=hyper,
                   consensus_backend=scfg.backend,
                   topology=scfg.topology.kind,
                   p_connect=scfg.topology.p_connect,
                   topology_seed=scfg.topology.seed,
                   consensus_compress=opts.get("compress"),
                   dp_sigma=opts.get("dp_sigma", 0.0),
                   q=scfg.q)

    @classmethod
    def coerce(cls, cfg, hyper: BilevelHyper | None = None):
        """Accept either an InteractConfig or a unified SolverConfig."""
        if isinstance(cfg, cls):
            return cfg
        return cls.from_solver_config(cfg, hyper=hyper)

    def consensus_engine(self, m: int, a_axes):
        """Build the distributed consensus engine for this config."""
        if self.consensus_backend != "ppermute":
            raise ValueError(
                f"backend {self.consensus_backend!r} cannot run inside "
                "shard_map; the distributed runtime requires 'ppermute' "
                "(dense/pallas serve the single-host simulator)")
        return make_engine("ppermute", self.mixing_spec(m),
                           agent_axes=tuple(a_axes),
                           compress=self.consensus_compress,
                           dp_sigma=self.dp_sigma)


def _zeros_like_tree(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def init_train_state(cfg: ArchConfig, key: jax.Array, m: int) -> TrainState:
    """Host-side init (used under jax.eval_shape for the dry-run, or for
    real small-scale runs).  All agents start from the same (x0, y0) as in
    Algorithm 1; u/v/p start at zero (first step's tracking difference
    makes u_1 = p_1, preserving the u-average invariant)."""
    kx, ky = jax.random.split(key)
    x0 = M.init_params(cfg, kx, with_head=False)
    y0 = M.init_head(cfg, ky)
    bcast = lambda t: jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (m,) + l.shape), t)
    x = bcast(x0)
    y = bcast(y0)
    return TrainState(x=x, y=y, u=_zeros_like_tree(x),
                      v=jnp.zeros_like(y), p_prev=_zeros_like_tree(x),
                      t=jnp.zeros((), jnp.int32))


def train_state_specs(state_shapes: TrainState, mesh,
                      agent_mode: str = "rows") -> TrainState:
    """PartitionSpecs for every leaf of the state.

    agent_mode="rows": agents = ("pod","data") rows (paper-default layout).
    agent_mode="pods": agents = pods only (perf P6) — each agent's state
    additionally shards over the pod-internal "data" axis (FSDP-style),
    cutting per-chip INTERACT state by the data-axis size.  This is the
    feasible layout for 100B+ architectures.
    """
    msize = mesh.shape["model"]
    if agent_mode == "pods":
        a_axes = ("pod",)
        extra = (("data", mesh.shape["data"]),)
    else:
        a_axes = agent_axes(mesh)
        extra = ()
    def x_tree_specs(tree):
        # The embedding gather trips XLA's SPMD partitioner when its table
        # is sharded over both model and data (CHECK failure in
        # PartitionGather on the CPU backend) — keep embed model-only and
        # FSDP-shard the layer stacks, which hold ~all the bytes.
        specs = {}
        for key, sub in tree.items():
            ex = extra if key == "layers" else ()
            specs[key] = stacked_tree_specs(sub, msize, a_axes, ex)
        return specs

    x_specs = x_tree_specs(state_shapes.x)
    y_spec = leaf_spec(state_shapes.y.shape, msize, a_axes,
                       agent_leading=True, extra_axes=extra)
    return TrainState(
        x=x_specs,
        y=y_spec,
        u=x_tree_specs(state_shapes.u),
        v=y_spec,
        p_prev=x_tree_specs(state_shapes.p_prev),
        t=P(),
    )


def _agent_entry(a_axes):
    return a_axes if len(a_axes) > 1 else a_axes[0]


def make_train_step(cfg: ArchConfig, mesh, icfg: InteractConfig,
                    *, with_prefix: bool = False, agent_mode: str = "rows"):
    """Returns step(state, tokens[, prefix]) -> (state, metrics).

    ``icfg`` may be an ``InteractConfig`` or a unified
    ``repro.solvers.SolverConfig`` (coerced via ``from_solver_config``),
    so the same config object drives the simulator and the LM runtime.

    tokens: (m, per_agent_batch, seq) int32 — first half of the batch is
    the inner split, second half the outer split.

    agent_mode="pods" (perf P6): the shard_map is manual over the pod
    axis only; "data" stays auto, so each agent's backbone math is
    batch-parallel over its pod's data rows and its parameters live
    FSDP-sharded over them (see train_state_specs).
    """
    icfg = InteractConfig.coerce(icfg)
    if agent_mode == "pods":
        a_axes = ("pod",)
    else:
        a_axes = agent_axes(mesh)
    m = 1
    for ax in a_axes:
        m *= mesh.shape[ax]
    aentry = _agent_entry(a_axes)
    hyper = icfg.hyper
    engine = icfg.consensus_engine(m, a_axes)

    def per_agent(state: TrainState, tokens, ids, prefix):
        # Leaves arrive with leading agent dim of local size 1; ``ids``
        # threads each agent's ring position in as data.
        sq = lambda t: jax.tree_util.tree_map(lambda l: l[0], t)
        un = lambda t: jax.tree_util.tree_map(lambda l: l[None], t)
        agent_idx = ids[0]

        dp_key = (jax.random.fold_in(jax.random.PRNGKey(0), state.t)
                  if icfg.dp_sigma > 0 else None)

        def grads_fn(x_new, y_new):
            # ---- Step 2: local gradients at the new iterate --------------
            toks = tokens[0]                       # (b, s) this agent
            # (pods mode: batch-parallelism is induced by the residual-
            # stream constraint inside features() — constraining the token
            # *indices* here trips XLA's gather partitioner, see
            # EXPERIMENTS.md P6.)
            half = toks.shape[0] // 2
            inner_t, outer_t = toks[:half], toks[half:]
            pre_in = pre_out = None
            if prefix is not None:
                pre = prefix[0]
                pre_in, pre_out = pre[:half], pre[half:]
            p_new, v_new, outer_ce = local_grads(
                cfg, hyper, sq(x_new), y_new[0], inner_t, outer_t,
                prefix_inner=pre_in, prefix_outer=pre_out)
            return un(p_new), v_new[None], outer_ce

        # Steps 1-3 via the shared step-core on the ppermute engine.
        # First iteration: p_prev is zero and u is zero, so Step 3 sets
        # u_1 = p_1 exactly (matches the Algorithm-1 init u_0 = p_0).
        x_new, y_new, u_new, v_new, p_new, _, outer_ce = (
            consensus_descent_and_track(
                engine, state.x, state.y, state.u, state.v, state.p_prev,
                icfg.alpha, icfg.beta, grads_fn, t=state.t, dp_key=dp_key,
                agent_index=agent_idx))

        # ---- metrics (replicated over agents) ----------------------------
        axis = aentry
        mean_ce = jax.lax.pmean(outer_ce, axis)
        gsq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                  for l in jax.tree_util.tree_leaves(u_new))
        grad_norm = jnp.sqrt(jax.lax.pmean(gsq, axis))

        new_state = TrainState(x=x_new, y=y_new, u=u_new, v=v_new,
                               p_prev=p_new, t=state.t + 1)
        return new_state, {"outer_ce": mean_ce, "grad_norm": grad_norm}

    def step(state: TrainState, tokens, prefix=None):
        # in/out specs: agent-leading dims manual, everything else auto.
        specs_state = jax.tree_util.tree_map(lambda _: P(aentry), state)
        specs_state = specs_state._replace(t=P())
        out_specs = (specs_state, {"outer_ce": P(), "grad_norm": P()})
        ids = jnp.arange(m, dtype=jnp.int32)
        if prefix is None:
            fn = jax.shard_map(
                lambda s, tk, ii: per_agent(s, tk, ii, None), mesh=mesh,
                in_specs=(specs_state, P(aentry), P(aentry)),
                out_specs=out_specs, axis_names=set(a_axes),
                check_vma=False)
            return fn(state, tokens, ids)
        fn = jax.shard_map(
            per_agent, mesh=mesh,
            in_specs=(specs_state, P(aentry), P(aentry), P(aentry)),
            out_specs=out_specs, axis_names=set(a_axes),
            check_vma=False)
        return fn(state, tokens, ids, prefix)

    return step


def make_eval_step(cfg: ArchConfig, mesh, icfg: InteractConfig):
    """Average outer CE over agents at the current iterate (no update)."""
    icfg = InteractConfig.coerce(icfg)
    a_axes = agent_axes(mesh)
    aentry = _agent_entry(a_axes)
    hyper = icfg.hyper

    def per_agent(state: TrainState, tokens):
        from repro.train.bilevel_lm import outer_loss
        sq = lambda t: jax.tree_util.tree_map(lambda l: l[0], t)
        ce = outer_loss(cfg, hyper, sq(state.x), state.y[0], tokens[0])
        return jax.lax.pmean(ce, aentry)

    def step(state, tokens):
        specs_state = jax.tree_util.tree_map(lambda _: P(aentry), state)
        specs_state = specs_state._replace(t=P())
        return jax.shard_map(per_agent, mesh=mesh,
                             in_specs=(specs_state, P(aentry)),
                             out_specs=P(),
                             axis_names=set(a_axes),
                             check_vma=False)(state, tokens)

    return step
