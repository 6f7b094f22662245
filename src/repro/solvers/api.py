"""The `Solver` protocol, registry, and scan-compiled experiment runner.

One API surface drives every Section-6 algorithm:

    from repro.solvers import SolverConfig, make_solver

    solver = make_solver(SolverConfig(algo="interact", alpha=0.3, beta=0.3))
    state  = solver.init(None, problem, hg_cfg, x0, y0, data)
    state  = solver.step(state, data)            # one jitted iteration
    state  = solver.run(state, data, 100)        # lax.scan, compiled once

``make_solver`` looks the algorithm up in the ``@register_solver``
registry — adding a fifth algorithm is one decorated class, not a new
copy of the init/step/build triple (see docs/SOLVERS.md).

The step and run closures are jitted with ``donate_argnums=0``: the
incoming state buffers are donated to the outputs, so the simulator hot
loop updates in place instead of allocating a fresh state per call.
``run`` wraps the *same* step body in ``lax.scan`` (static ``num_steps``)
so a multi-step experiment dispatches one XLA computation instead of one
Python call per iteration, and ``run_traced`` additionally folds metric
recording into that scan (``lax.cond`` every ``record_every`` steps) so
a whole recorded experiment is a single program — the batched sweep
engine (``repro.solvers.sweep``, docs/SWEEPS.md) vmaps it over config
grids.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro import tracing
from repro.consensus import make_engine
from repro.solvers.config import SolverConfig

__all__ = [
    "Solver",
    "SolverBase",
    "SolveResult",
    "available_solvers",
    "default_setup",
    "make_solver",
    "register_solver",
    "run_recorded",
    "solve",
]


def _traced_scan(param_step, state, data, num_steps: int, record_every: int,
                 metric_fn, alpha, beta):
    """One ``lax.scan`` that steps the solver *and* records the metric.

    The carry is the solver state; the stacked scan output is the
    per-step metric, computed every ``record_every`` steps through
    ``lax.cond`` (so off-boundary steps pay nothing) and ``NaN``-padded
    otherwise.  After the scan the padded column is compacted **on
    device** to the legacy ``run_recorded`` layout — metric before steps
    ``0, record_every, 2*record_every, ...`` plus the final iterate — so
    the whole experiment (stepping + recording) is a single XLA program
    with no host round-trips.

    ``param_step(state, data, alpha, beta)`` is the raw parameterised
    step body; ``alpha`` / ``beta`` may be traced scalars, which is what
    lets ``sweep`` vmap experiments over step sizes.

    Returns ``(final_state, trace)``; ``trace`` is an empty array when
    ``metric_fn`` is None.
    """
    chunk = record_every if record_every else num_steps

    if metric_fn is None:
        def body(s, _):
            return param_step(s, data, alpha, beta), None

        state, _ = jax.lax.scan(body, state, xs=None, length=num_steps)
        return state, jnp.zeros((0,), jnp.float32)

    aval = jax.eval_shape(metric_fn, state)
    dtype = aval.dtype

    def body(s, i):
        val = jax.lax.cond(
            (i % chunk) == 0,
            lambda st: jnp.asarray(metric_fn(st), dtype),
            lambda st: jnp.asarray(jnp.nan, dtype), s)
        return param_step(s, data, alpha, beta), val

    state, padded = jax.lax.scan(body, state, xs=jnp.arange(num_steps))
    final = jnp.asarray(metric_fn(state), dtype)
    trace = jnp.concatenate([padded[::chunk], final[None]])
    return state, trace

_REGISTRY: dict[str, type] = {}


def register_solver(name: str) -> Callable[[type], type]:
    """Class decorator: register a Solver implementation under ``name``."""

    def deco(cls: type) -> type:
        existing = _REGISTRY.get(name)
        if existing is not None and existing is not cls:
            raise ValueError(f"solver {name!r} already registered "
                             f"({existing.__name__})")
        _REGISTRY[name] = cls
        cls.name = name
        return cls

    return deco


def available_solvers() -> tuple[str, ...]:
    """Registered algorithm names, sorted."""
    return tuple(sorted(_REGISTRY))


def make_solver(config: SolverConfig) -> "Solver":
    """Instantiate the registered solver for ``config.algo``."""
    try:
        cls = _REGISTRY[config.algo]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {config.algo!r}; "
            f"choose from {available_solvers()}") from None
    return cls(config)


@runtime_checkable
class Solver(Protocol):
    """What every registry algorithm exposes.

    ``init`` binds the problem instance (building the consensus engine and
    compiling the step/run closures) and returns the initial state;
    ``step`` advances one iteration; ``run`` advances ``num_steps``
    iterations inside one ``lax.scan``.  ``samples_per_step(n)`` is the
    per-agent IFO cost of one iteration (Definition 1) on an n-sample
    local dataset; ``communications_per_step`` the consensus rounds per
    iteration (Definition 2).
    """

    config: SolverConfig
    communications_per_step: int

    def init(self, key, problem, hg_cfg, x0, y0, data) -> Any: ...

    def step(self, state, data) -> Any: ...

    def run(self, state, data, num_steps: int) -> Any: ...

    def run_traced(self, state, data, num_steps: int, record_every: int = 0,
                   metric_fn=None) -> Any: ...

    def samples_per_step(self, n: int) -> float: ...

    def hypergrad_calls_per_step(self, n: int) -> float: ...


class SolverBase:
    """Shared plumbing: engine construction, jit + donation, scan runner.

    Subclasses implement ``_init_state`` and ``_make_step`` (returning the
    raw python step body over a bound ``ConsensusEngine``); everything
    else — registry construction, closure compilation, the scan runner,
    warmup — lives here once.
    """

    communications_per_step = 2  # Steps 1 and 3 each mix once

    def __init__(self, config: SolverConfig):
        self.config = config
        self._step_fn = None
        self._run_fn = None
        self._traced_fn = None
        self._chunk_fn = None
        self._param_step = None
        self._engine = None

    # -- subclass hooks ---------------------------------------------------
    def _init_state(self, key, problem, hg_cfg, x0, y0, data):
        raise NotImplementedError

    def _make_param_step(self, problem, hg_cfg, engine, n: int | None):
        """Return the raw ``step(state, data, alpha, beta) -> state``.

        The registry solvers implement this form: alpha/beta enter the
        body as (possibly traced) scalars instead of baked-in closure
        constants, so the sweep engine can ``vmap`` one compiled step
        over a batch of step sizes.  Solvers that predate the hook may
        override ``_make_step`` instead; they then lose only the
        step-size batch axis (``sweep`` keys their groups on alpha/beta).
        """
        raise NotImplementedError

    def _make_step(self, problem, hg_cfg, engine, n: int | None):
        """Return the raw (non-jitted) ``step(state, data) -> state``.

        Default: bind ``config.alpha`` / ``config.beta`` into the
        parameterised body from ``_make_param_step`` (reusing the one
        ``build`` already constructed for this engine when available).
        """
        param = (self._param_step if self._param_step is not None
                 else self._make_param_step(problem, hg_cfg, engine, n))
        alpha, beta = self.config.alpha, self.config.beta

        def step(state, data):
            return param(state, data, alpha, beta)

        return step

    # -- construction -----------------------------------------------------
    def build(self, problem, hg_cfg=None, *, m: int | None = None,
              n: int | None = None) -> "SolverBase":
        """Bind the problem + network and compile the step/run closures.

        ``init`` calls this automatically (deriving m, n from the data);
        call it directly only when constructing a step function without
        data in hand (the legacy ``make_*_step`` shims do).
        """
        hg_cfg = hg_cfg if hg_cfg is not None else self.config.hypergrad
        hg_cfg.resolve_backend()   # fail fast on unknown engine names
        spec = self.config.mixing_spec(m)
        if m is not None and spec.num_agents != m:
            # fail here, not as an XLA dot-shape error deep in the first
            # mix: config-declared network vs data-derived m disagree
            raise ValueError(
                f"config declares a {spec.num_agents}-agent network "
                f"(num_agents/mixing) but the data carries m={m} agents")
        engine = make_engine(
            self.config.backend, spec,
            compression=self.config.compression,
            communication_interval=self.config.communication_interval,
            byzantine=self.config.byzantine,
            **dict(self.config.backend_opts))
        if self.config.byzantine.attack_active:
            # the attack schedule inherits the solver seed unless the
            # ByzantineConfig pins its own
            engine.byz_values["key"] = jax.random.PRNGKey(
                self.config.byzantine.resolve_seed(self.config.seed))
        if not self.config.topology_process.is_static:
            from repro.topology import attach_topology
            attach_topology(engine, self.config.topology_process, spec,
                            seed=self.config.seed)
        self._engine = engine
        try:
            self._param_step = self._make_param_step(problem, hg_cfg,
                                                     engine, n)
        except NotImplementedError:
            self._param_step = None
        if self.config.guard.active:
            if self._param_step is None:
                raise ValueError(
                    f"GuardConfig is active but solver {self.name!r} "
                    f"implements no parameterised step to wrap")
            from repro.byzantine import guard_param_step
            self._param_step = guard_param_step(self._param_step,
                                                self.config.guard)
        raw = tracing.scoped(tracing.STEP)(
            self._make_step(problem, hg_cfg, engine, n))
        self._raw_step = raw
        self._step_fn = jax.jit(raw, donate_argnums=0)

        def scan_run(state, data, num_steps):
            def body(s, _):
                return raw(s, data), None

            out, _ = jax.lax.scan(body, state, xs=None, length=num_steps)
            return out

        self._run_fn = jax.jit(scan_run, static_argnums=2, donate_argnums=0)

        def traced_run(state, data, num_steps, record_every, metric_fn):
            def param(s, d, _a, _b):
                return raw(s, d)

            return _traced_scan(param, state, data, num_steps, record_every,
                                metric_fn, self.config.alpha,
                                self.config.beta)

        self._traced_fn = jax.jit(traced_run, static_argnums=(2, 3, 4),
                                  donate_argnums=0)

        def chunk_run(state, data, chunk_len, record_mod, metric_fn,
                      start_step):
            """One checkpoint-interval chunk of the resumable runner.

            Identical step body to ``traced_run`` but (a) the scan index
            is offset by the traced ``start_step`` — the global step the
            incoming carry sits at — so the metric fires on the same
            global boundaries whatever chunk the run was cut into, and
            (b) the per-step metric column comes back *uncompacted*
            (NaN off-boundary): compaction needs the whole run, which
            the resilience runner assembles across chunks
            (docs/RESILIENCE.md).  ``start_step`` being a traced operand
            means every equal-length chunk shares one compile.
            """
            if metric_fn is None:
                def body(s, _):
                    return raw(s, data), None

                state, _ = jax.lax.scan(body, state, xs=None,
                                        length=chunk_len)
                return state, jnp.zeros((0,), jnp.float32)
            dtype = jax.eval_shape(metric_fn, state).dtype

            def body(s, i):
                val = jax.lax.cond(
                    (i % record_mod) == 0,
                    lambda st: jnp.asarray(metric_fn(st), dtype),
                    lambda st: jnp.asarray(jnp.nan, dtype), s)
                return raw(s, data), val

            xs = jnp.asarray(start_step, jnp.int32) + jnp.arange(chunk_len)
            return jax.lax.scan(body, state, xs=xs)

        self._chunk_fn = jax.jit(chunk_run, static_argnums=(2, 3, 4),
                                 donate_argnums=0)
        self._metric_jits: dict[int, Any] = {}
        self._problem, self._hg_cfg = problem, hg_cfg
        return self

    def metric_eval(self, metric_fn, state):
        """``metric_fn(state)`` under jit (cached per metric closure).

        The resilience runner's final-record evaluation: bitwise-equal
        to the in-program ``metric_fn(final_state)`` the one-scan
        ``run_traced`` computes.
        """
        fn = self._metric_jits.get(id(metric_fn))
        if fn is None:
            fn = self._metric_jits[id(metric_fn)] = jax.jit(metric_fn)
        return fn(state)

    def init(self, key, problem, hg_cfg, x0, y0, data):
        """Build the solver for this problem and return the initial state.

        ``key=None`` derives the sampling key from ``config.seed``;
        ``hg_cfg=None`` falls back to ``config.hypergrad``.
        """
        m = data.inner_x.shape[0]
        # n is the full per-agent dataset (inner + outer splits): the
        # paper's q = |S| = ceil(sqrt(n)) defaults are taken against it.
        n = data.inner_x.shape[1] + data.outer_x.shape[1]
        self.build(problem, hg_cfg, m=m, n=n)
        if key is None:
            key = jax.random.PRNGKey(self.config.seed)
        return self._init_state(key, self._problem, self._hg_cfg, x0, y0,
                                data)

    # -- stepping ---------------------------------------------------------
    def step(self, state, data):
        """One jitted iteration (state buffers donated)."""
        if self._step_fn is None:
            raise RuntimeError("call init()/build() before step()")
        return self._step_fn(state, data)

    def run(self, state, data, num_steps: int, *,
            checkpoint_every: int | None = None, ckpt_dir=None):
        """``num_steps`` iterations under one jitted ``lax.scan``.

        ``checkpoint_every`` chunks the scan at checkpoint boundaries
        and snapshots the complete solver carry into ``ckpt_dir`` after
        each chunk (atomic, CRC-checked — see docs/RESILIENCE.md), so a
        killed run resumes from its last boundary via
        ``repro.resilience.resume_run``.  Every equal-length chunk
        shares one compile; the final state is bitwise-equal to the
        unchunked scan.
        """
        if self._run_fn is None:
            raise RuntimeError("call init()/build() before run()")
        with tracing.host_span(tracing.RUN):
            if checkpoint_every:
                from repro.resilience import run_resumable
                state, _, _ = run_resumable(
                    self, state, data, num_steps,
                    checkpoint_every=checkpoint_every, ckpt_dir=ckpt_dir)
                return state
            return self._run_fn(state, data, num_steps)

    def run_traced(self, state, data, num_steps: int, record_every: int = 0,
                   metric_fn=None, *, checkpoint_every: int | None = None,
                   ckpt_dir=None):
        """``num_steps`` iterations with the metric recorded *in-scan*.

        One jitted XLA program (state donated) steps the solver and
        evaluates ``metric_fn(state) -> scalar`` every ``record_every``
        steps (plus the final iterate) on device — no per-chunk host
        loop, no intermediate ``block_until_ready``, no recompiles for
        remainder chunk lengths.  ``metric_fn`` must be traceable (see
        ``repro.core.convergence_metric_fn``) and is a static jit
        argument: pass a stable closure, not a fresh lambda per call.

        Returns ``(state, trace)`` where ``trace`` is a device array
        laid out exactly like the legacy ``run_recorded`` list — metric
        before steps ``0, record_every, ...`` then after the last step —
        or an empty array when ``metric_fn`` is None.

        ``checkpoint_every`` routes through the resilience runner: the
        scan is cut at checkpoint boundaries (every equal-length chunk
        one compile), the complete carry plus the partial metric column
        is snapshotted into ``ckpt_dir`` after each chunk, and the
        returned trace is bitwise-equal to the unchunked program — the
        contract ``repro.resilience`` kill/resume parity is built on
        (docs/RESILIENCE.md).  Meant for fresh states (the global step
        offset is taken from ``state.t``); resuming an interrupted run
        goes through ``repro.resilience.resume_run``.
        """
        if self._traced_fn is None:
            raise RuntimeError("call init()/build() before run_traced()")
        if checkpoint_every:
            from repro.resilience import run_resumable
            state, trace, _ = run_resumable(
                self, state, data, num_steps, record_every, metric_fn,
                checkpoint_every=checkpoint_every, ckpt_dir=ckpt_dir)
            return state, trace
        return self._traced_fn(state, data, num_steps, record_every,
                               metric_fn)

    def warmup(self, state, data, num_steps: int | None = None) -> None:
        """Compile ``step`` (or ``run`` at ``num_steps``) without consuming
        ``state``: the donated argument is a copy, the result discarded.

        The copy is an explicit ``jnp.copy`` — ``jnp.array`` may return
        the input buffer unchanged on some JAX versions, and an aliased
        "copy" would let donation invalidate the caller's state.
        """
        copy = jax.tree_util.tree_map(jnp.copy, state)
        out = (self.step(copy, data) if num_steps is None
               else self.run(copy, data, num_steps))
        jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])

    def samples_per_step(self, n: int) -> float:
        raise NotImplementedError

    # Hypergradient evaluations per iteration (amortized): how many times
    # the algorithm invokes the eq.-(5)/(22) estimator per agent per step.
    # Multiplied by the engine's *measured* per-call HypergradStats this
    # yields the per-step hvp/grad counts that `solve` and the bench
    # harness report (Theorem-1/2 accounting, see docs/HYPERGRAD.md).
    def hypergrad_calls_per_step(self, n: int) -> float:
        return 1.0


def run_recorded(solver, state, data, num_steps: int, record_every: int = 0,
                 metric_fn=None, scan: bool = True):
    """Chunked timed runner shared by ``solve`` and the bench harness.

    Advances ``num_steps`` iterations in ``record_every``-sized chunks —
    through the scan-compiled ``solver.run`` (one compile per distinct
    chunk length), or the per-step python loop with ``scan=False``.
    Compilation happens on a throwaway state copy before the timer
    starts, and ``metric_fn(state) -> float`` (if given) is evaluated
    *between* timed chunks, so the returned seconds measure stepping
    only.  Returns ``(state, trace, seconds)``.
    """
    chunk = record_every if record_every else num_steps
    lengths = [chunk] * (num_steps // chunk)
    if num_steps % chunk:
        lengths.append(num_steps % chunk)
    if scan:
        for length in sorted(set(lengths)):
            solver.warmup(state, data, length)
    else:
        solver.warmup(state, data)

    trace, took = [], 0.0
    for length in lengths:
        if metric_fn is not None:
            trace.append(metric_fn(state))
        t0 = time.perf_counter()
        if scan:
            state = solver.run(state, data, length)
        else:
            for _ in range(length):
                state = solver.step(state, data)
        jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
        took += time.perf_counter() - t0
    if metric_fn is not None:
        trace.append(metric_fn(state))
    return state, trace, took


@dataclasses.dataclass
class SolveResult:
    """What ``solve`` returns: final state plus the experiment record."""

    state: Any
    trace: list[float]          # convergence metric every record_every steps
    us_per_step: float          # stepping time only (metrics excluded)
    samples_per_step: float     # per-agent IFO cost (Definition 1)
    communications_per_step: int
    # wire bytes one agent ships per consensus round under the engine's
    # compressor (engine.bytes_on_wire of the per-agent x payload) —
    # Definition-2 round counts priced in bytes.  Warmup / interval
    # scheduling is not folded in (see consensus.cumulative_wire_bytes).
    bytes_per_round: float = 0.0
    # measured per-agent hypergradient accounting (one step, amortized):
    # the engine's counted per-call HypergradStats at the initial iterate
    # times the algorithm's hypergrad calls per step — what Theorems 1-2
    # charge for, measured instead of inferred (docs/HYPERGRAD.md).
    hvp_per_step: float = 0.0
    grad_per_step: float = 0.0
    hess_per_step: float = 0.0
    # divergence-guard counters (SolverConfig.guard): how many scan
    # steps tripped a wire and were rolled back, and the step counter of
    # the last accepted state.  0 / -1 when no guard was configured —
    # time-to-detection is last_good_step vs num_steps.
    tripped_steps: int = 0
    last_good_step: int = -1
    # MEASURED communication, from the CommsLedger attached to the engine
    # before the step was traced: per-agent bytes the compiled program
    # actually shipped over the run (trace-time payload capture + the
    # host-replayed warmup/interval schedule — consensus/ledger.py), and
    # the median wall-clock of one warmed jitted consensus round.  None
    # when the backend cannot be timed outside shard_map (latency) —
    # bytes are recorded for every backend.
    measured_wire_bytes: float | None = None
    round_latency_us: float | None = None


def default_setup(seed: int = 0, num_agents: int = 5, n_per_agent: int = 600,
                  d_in: int = 16, hidden: int = 20, classes: int = 5):
    """The paper's Section-6 synthetic meta-learning instance.

    Returns ``(problem, x0, y0, data)`` — the default experiment that
    ``solve`` and ``sweep`` fall back to when no problem is supplied.
    """
    from repro.core import (MLPMetaProblem, init_head, init_mlp_backbone,
                            make_synthetic_agents)
    key = jax.random.PRNGKey(seed)
    data = make_synthetic_agents(key, num_agents=num_agents,
                                 n_per_agent=n_per_agent, d_in=d_in,
                                 num_classes=classes)
    problem = MLPMetaProblem(mu_g=0.5, lipschitz_g=4.0)
    x0 = init_mlp_backbone(jax.random.PRNGKey(seed + 1), d_in, hidden=hidden)
    y0 = init_head(jax.random.PRNGKey(seed + 2), hidden, classes)
    return problem, x0, y0, data


def solve(config: SolverConfig, num_steps: int, record_every: int = 0,
          *, problem=None, hg_cfg=None, x0=None, y0=None, data=None,
          num_agents: int = 5, n_per_agent: int = 600,
          metric_fn=None, measure_hypergrad: bool | None = None
          ) -> SolveResult:
    """End-to-end experiment: build, init, scan-run, record.

    With only ``(config, num_steps, record_every)`` this reproduces the
    paper's Section-6 synthetic meta-learning setup (m agents, n samples
    per agent, the MLP problem, the eq.-11 convergence metric); pass
    ``problem``/``x0``/``y0``/``data`` to run on your own instance, and
    ``metric_fn(state) -> float`` to record a custom metric.

    Stepping runs through ``solver.run`` in ``record_every``-sized chunks
    (one compile per distinct chunk length); metric evaluation happens
    outside the timed window.

    Besides timing and the Definition-1/2 sample/communication costs,
    the result carries *measured* per-step hypergradient accounting
    (``hvp_per_step`` / ``grad_per_step`` / ``hess_per_step``): one
    counted engine call (``repro.hypergrad.measure_counts``) at the
    initial iterate times the algorithm's amortized estimator calls per
    step — see docs/HYPERGRAD.md.  The measurement is one eager
    estimator evaluation (a small fixed key set for stochastic-k
    configs), so ``measure_hypergrad`` defaults to ``record_every > 0``:
    callers that record nothing (sweep loops that only want the final
    state or their own timing) are not charged for accounting they would
    discard.  Pass True/False to force it either way (the count fields
    stay 0 when skipped).
    """
    if measure_hypergrad is None:
        measure_hypergrad = record_every > 0
    if problem is None or data is None or x0 is None or y0 is None:
        problem, x0, y0, data = default_setup(
            config.seed,
            num_agents=config.resolve_num_agents(num_agents),
            n_per_agent=n_per_agent)

    solver = make_solver(config)
    state = solver.init(None, problem, hg_cfg, x0, y0, data)
    # jit is lazy, so attaching after init/build still precedes the first
    # trace — every wire stream the compiled step ships gets recorded
    from repro.consensus import attach_ledger
    ledger = attach_ledger(solver._engine)

    if metric_fn is None and record_every:
        from repro.core import convergence_metric

        def metric_fn(st):
            rep = convergence_metric(solver._problem, solver._hg_cfg,
                                     st.x, st.y, 300, 0.5, data)
            return float(rep.total)

    state, trace, took = run_recorded(solver, state, data, num_steps,
                                      record_every, metric_fn)

    n = data.inner_x.shape[1] + data.outer_x.shape[1]
    counts = {}
    if measure_hypergrad:
        from repro.hypergrad import measure_problem_counts
        per_call = measure_problem_counts(problem, solver._hg_cfg, x0, y0,
                                          data)
        calls = solver.hypergrad_calls_per_step(n)
        counts = dict(hvp_per_step=per_call.hvp_count * calls,
                      grad_per_step=per_call.grad_count * calls,
                      hess_per_step=per_call.hess_count * calls)
    guard = getattr(state, "guard", None)
    if guard is not None:
        counts.update(tripped_steps=int(guard["tripped"]),
                      last_good_step=int(guard["last_good"]))
    ledger.commit_steps(num_steps)
    if solver._engine.name in ("dense", "pallas"):
        # single-host matrix backends mix outside shard_map, so one
        # warmed jitted combine times cleanly; mesh backends report
        # latency through the launch layer instead (docs/DISTRIBUTED.md)
        from repro.consensus import time_round_us
        engine = solver._engine
        ledger.observe_latency(time_round_us(
            jax.jit(lambda tr: engine.mix(tr)), state.x))
    # one agent's consensus payload: its slice of the outer iterate tree
    payload = jax.tree_util.tree_map(lambda l: l[0], state.x)
    return SolveResult(state=state, trace=trace,
                       us_per_step=1e6 * took / max(num_steps, 1),
                       samples_per_step=solver.samples_per_step(n),
                       communications_per_step=solver.communications_per_step,
                       bytes_per_round=float(
                           solver._engine.bytes_on_wire(payload)),
                       measured_wire_bytes=ledger.measured_wire_bytes,
                       round_latency_us=ledger.round_latency_us,
                       **counts)
