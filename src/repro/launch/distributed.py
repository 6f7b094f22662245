"""Multi-process mesh launch path: ``jax.distributed`` + shard_map solver.

The simulator (``repro.solvers``) runs every agent in one process; the
mesh backends (ppermute, allgather) already mix *inside* ``shard_map``
but the repo never stood up an actual multi-process run.  This module
closes that gap:

* ``initialize`` / ``initialize_from_env`` — ``jax.distributed``
  bring-up (gloo CPU collectives), idempotent, driven by CLI args or the
  ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
  env vars the localhost driver (scripts/launch_local.py) exports.
* ``agent_mesh`` — the global device mesh over ``jax.devices()`` (which
  spans every process after initialize), agents on the ``data`` axis.
* ``MeshRunner`` — any built registry solver (any ``BilevelProblem``)
  stepped on the mesh: its raw step body wrapped in a *full-manual*
  shard_map, one agent per ``data`` row, state initialised on the mesh
  from one initial point or put there from the host.
* ``run_section6`` — the paper's Section-6 synthetic instance through a
  ``MeshRunner``, with the eq.-11 stationarity metric recorded
  host-side and a
  ``CommsLedger`` measuring the bytes the compiled program actually
  ships (docs/DISTRIBUTED.md).

Everything here must run in lockstep on every process: the same
``run_section6`` call with the same arguments, so each process computes
the identical host-side setup (same seeds) and participates in every
collective.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_production_mesh

__all__ = [
    "DistributedConfig",
    "MeshRunner",
    "agent_mesh",
    "initialize",
    "initialize_from_env",
    "run_section6",
    "shard_host_tree",
    "shutdown",
]

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

_initialized = False


@dataclasses.dataclass
class DistributedConfig:
    """Where this process sits in the multi-process run."""

    coordinator: str = "127.0.0.1:12355"
    num_processes: int = 1
    process_id: int = 0


def initialize(config: DistributedConfig) -> bool:
    """``jax.distributed.initialize`` for this process (idempotent).

    Must run before anything touches jax device state (``jax.devices``,
    any computation) — the backend is finalised on first use.  Selects
    the gloo CPU collectives implementation so cross-process psum /
    ppermute / all_gather lower on the CPU backend.  Returns True when a
    distributed runtime is (now) up.
    """
    global _initialized
    if _initialized:
        return True
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=config.coordinator,
        num_processes=int(config.num_processes),
        process_id=int(config.process_id))
    _initialized = True
    return True


def initialize_from_env() -> bool:
    """Initialize from ``REPRO_*`` env vars; no-op without them.

    The localhost driver exports them for every worker; single-process
    callers (tests, the simulator) simply never set them.
    """
    coord = os.environ.get(ENV_COORDINATOR)
    nproc = int(os.environ.get(ENV_NUM_PROCESSES, "0") or 0)
    if coord is None or nproc < 1:
        return False
    return initialize(DistributedConfig(
        coordinator=coord, num_processes=nproc,
        process_id=int(os.environ.get(ENV_PROCESS_ID, "0"))))


def shutdown() -> None:
    """Tear the distributed runtime down (idempotent)."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def agent_mesh(num_agents: int):
    """The global mesh with agents on ``data``: ``(m,)`` or ``(m, k)``.

    ``jax.devices()`` spans every process after ``initialize``; the
    device count must be a multiple of ``num_agents`` (the surplus
    becomes the model axis).  Raises an actionable error otherwise.
    """
    devices = jax.devices()
    n = len(devices)
    m = int(num_agents)
    if m < 1 or n < m or n % m:
        hint = f"pick m from the divisors of {n}"
        if devices[0].platform == "cpu":
            hint += (", or relaunch with --devices-per-process so processes "
                     "x devices is a multiple of m (scripts/launch_local.py)")
        raise ValueError(
            f"num_agents={m} does not divide the {n} mesh devices — {hint}")
    model = n // m
    shape = (m,) if model == 1 else (m, model)
    return make_production_mesh(shape=shape)


def _leaf_spec(leaf, num_agents: int):
    nd = getattr(leaf, "ndim", 0)
    shaped = nd and leaf.shape[0] == num_agents
    return P("data") if shaped else P()


def shard_host_tree(mesh, tree, num_agents: int):
    """Host (numpy) tree -> global jax.Arrays on ``mesh``.

    Leaves with a leading agent dim go ``P("data")``, everything else
    replicated.  Every process must hold the identical host tree (same
    seeds) and call this in lockstep; each fills only its addressable
    shards (``jax.make_array_from_callback``).
    """

    def put(leaf):
        host = np.asarray(leaf)
        sharding = NamedSharding(mesh, _leaf_spec(host, num_agents))
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx, h=host: h[idx])

    return jax.tree_util.tree_map(put, tree)


def _spec_tree(tree, num_agents: int):
    return jax.tree_util.tree_map(
        lambda l: _leaf_spec(l, num_agents), tree)


def _make_gather(mesh):
    """Host-gather closure: ``P("data")``-sharded tree -> full numpy.

    A jitted identity with replicated ``out_shardings`` — XLA inserts
    the cross-process all-gather; every process gets the same bytes.
    One closure per mesh so repeated metric evaluations reuse the
    compile (jit caches per input structure).
    """
    rep = NamedSharding(mesh, P())
    ident = jax.jit(lambda t: t, out_shardings=rep)

    def gather(tree):
        return jax.tree_util.tree_map(
            np.asarray, jax.device_get(ident(tree)))

    return gather


def _digest(host_tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(host_tree):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


class MeshRunner:
    """A built registry solver stepped on the device mesh, one agent per
    ``data`` row: its raw step inside a full-manual ``shard_map``.

    Runs any ``BilevelProblem`` the solver was built for.  State and data
    leaves carry the agent axis first and are sharded ``P("data")``
    (scalars such as the step counter replicated), so inside the map each
    device holds its agent's slice and the mesh consensus backends
    (``ppermute``, ``allgather``) exchange across devices.

    ``batch_of(data, t) -> data`` picks the step's batch from ``data`` by
    the state's step counter ``t`` (a pool of batches resident on the
    devices, cycled with no host feed); ``None`` steps on ``data`` as
    given.
    """

    def __init__(self, solver, num_agents: int, *, batch_of=None,
                 mesh=None):
        if getattr(solver, "_raw_step", None) is None:
            raise ValueError("build the solver (solver.build / init) first")
        self.solver = solver
        self.m = int(num_agents)
        self.mesh = mesh if mesh is not None else agent_mesh(self.m)
        self.batch_of = batch_of
        self._runs: dict = {}

    def put(self, host_tree):
        """Host tree -> global arrays on the mesh (``shard_host_tree``)."""
        return shard_host_tree(self.mesh, host_tree, self.m)

    def agent_specs(self, tree):
        """``P("data")`` for agent-stacked leaves, ``P()`` for scalars."""
        return jax.tree_util.tree_map(
            lambda l: P("data") if getattr(l, "ndim", 0) else P(), tree)

    def smap(self, fn, in_specs, out_specs):
        """``fn`` in a full-manual shard_map over this runner's mesh."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             axis_names=set(self.mesh.axis_names),
                             check_vma=False)

    def _batch(self, data, t):
        return data if self.batch_of is None else self.batch_of(data, t)

    def init_fn(self, point_shapes, data_shapes):
        """Jitted ``init(point, data) -> state``: the solver's
        ``_init_state`` on every agent from the one initial point
        ``point = (x0, y0)``, replicated on every device (``P()``)."""
        solver = self.solver

        def local(point, d):
            x0, y0 = point
            return solver._init_state(None, solver._problem, solver._hg_cfg,
                                      x0, y0, self._batch(d, 0))

        pspec = jax.tree_util.tree_map(lambda _: P(), point_shapes)
        dspec = self.agent_specs(data_shapes)
        local_data = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((1,) + l.shape[1:], l.dtype),
            data_shapes)
        sspec = self.agent_specs(jax.eval_shape(local, point_shapes,
                                                local_data))
        return jax.jit(self.smap(local, (pspec, dspec), sspec))

    def run(self, state, data, num_steps: int):
        """``num_steps`` solver steps in one jitted scan (state donated).

        Returns ``(state, t)``: ``t`` is a copy of the step counter in a
        buffer of its own, which a caller may wait on after the state has
        been donated to the next call.
        """
        fn = self._runs.get(num_steps)
        if fn is None:
            raw = self.solver._raw_step
            sspec = self.agent_specs(state)
            dspec = self.agent_specs(data)
            step = self.smap(lambda s, d: raw(s, self._batch(d, s.t)),
                             (sspec, dspec), sspec)

            def chunk(s, d):
                out, _ = jax.lax.scan(lambda c, _: (step(c, d), None), s,
                                      xs=None, length=num_steps)
                return out, jnp.copy(out.t)

            fn = self._runs[num_steps] = jax.jit(chunk, donate_argnums=0)
        return fn(state, data)


def run_section6(*, num_agents: int = 8, num_steps: int = 30,
                 record_every: int = 10, backend: str = "allgather",
                 compression=None, communication_interval: int = 1,
                 seed: int = 0, n_per_agent: int = 80, d_in: int = 8,
                 hidden: int = 8, classes: int = 3,
                 alpha: float = 0.1, beta: float = 0.1,
                 metric_inner_steps: int = 120,
                 metric_inner_lr: float = 0.5,
                 latency_reps: int = 5) -> dict:
    """Section-6 INTERACT on the device mesh, measured end to end.

    Builds the synthetic instance and the registry solver identically on
    every process, shards state/data over ``agent_mesh(num_agents)``,
    wraps the solver's raw step in full-manual shard_map, scans it in
    record_every chunks, and evaluates the eq.-11 stationarity metric
    host-side between chunks on the gathered iterates — the *same*
    ``convergence_metric`` computation the single-process baseline runs,
    so matched runs agree to float tolerance and identical-program runs
    agree bitwise (the ``digest`` field).

    A ``CommsLedger`` is attached before the trace, so the returned
    ``measured_wire_bytes`` is what the compiled program shipped;
    ``priced_wire_bytes`` is the broadcast model
    (``cumulative_wire_bytes``) and ``per_link_priced_bytes`` the
    ppermute unicast model — the ``check_distributed`` gate reconciles
    measured against the model matching the backend.

    Returns a JSON-ready dict (identical on every process apart from
    ``round_latency_us``, which is this process's own timing).
    """
    from repro.consensus import attach_ledger, cumulative_wire_bytes, \
        time_round_us
    from repro.core import convergence_metric
    from repro.solvers import SolverConfig, make_solver
    from repro.solvers.api import default_setup

    if backend not in ("allgather", "ppermute"):
        raise ValueError(
            f"the mesh runner drives the shard_map backends "
            f"('allgather', 'ppermute'), got {backend!r}")

    m = int(num_agents)
    mesh = agent_mesh(m)
    problem, x0, y0, data = default_setup(
        seed, num_agents=m, n_per_agent=n_per_agent, d_in=d_in,
        hidden=hidden, classes=classes)

    config = SolverConfig(
        algo="interact", alpha=alpha, beta=beta, num_agents=m,
        backend=backend, backend_opts={"agent_axes": ("data",)},
        compression=compression,
        communication_interval=communication_interval, seed=seed)
    solver = make_solver(config)
    state = solver.init(None, problem, None, x0, y0, data)
    ledger = attach_ledger(solver._engine)

    runner = MeshRunner(solver, m, mesh=mesh)
    host_state = jax.tree_util.tree_map(np.asarray, jax.device_get(state))
    host_data = jax.tree_util.tree_map(np.asarray, jax.device_get(data))
    gstate = runner.put(host_state)
    gdata = runner.put(host_data)
    gather = _make_gather(mesh)

    def metric(gs) -> float:
        host = gather({"x": gs.x, "y": gs.y})
        rep = convergence_metric(problem, solver._hg_cfg, host["x"],
                                 host["y"], metric_inner_steps,
                                 metric_inner_lr, data)
        return float(rep.total)

    step_chunk = record_every if record_every else num_steps
    lengths = [step_chunk] * (num_steps // step_chunk)
    if num_steps % step_chunk:
        lengths.append(num_steps % step_chunk)

    trace = []
    with jax.set_mesh(mesh):
        for length in lengths:
            trace.append(metric(gstate))
            gstate, _ = runner.run(gstate, gdata, length)
        final_metric = metric(gstate)
        trace.append(final_metric)

        engine = solver._engine
        xspec = _spec_tree(host_state.x, m)
        mix_fn = jax.jit(runner.smap(lambda t: engine.mix(t), (xspec,),
                                     xspec))
        ledger.observe_latency(
            time_round_us(mix_fn, gstate.x, reps=latency_reps))

        host_x = gather(gstate.x)

    ledger.commit_steps(num_steps)
    payload_entries = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(x0))
    priced = cumulative_wire_bytes(
        engine.compression, payload_entries, num_steps,
        comms_per_step=solver.communications_per_step,
        communication_interval=communication_interval)[-1]
    per_agent_payload = jax.tree_util.tree_map(lambda l: l[0], host_state.x)
    per_link = (solver.communications_per_step * num_steps
                * engine.bytes_on_wire(per_agent_payload))

    return {
        "backend": backend,
        "num_agents": m,
        "num_processes": jax.process_count(),
        "num_devices": len(jax.devices()),
        "mesh_shape": dict(mesh.shape),
        "num_steps": num_steps,
        "compression": engine.compression.kind,
        "final_metric": final_metric,
        "trace": trace,
        "digest": _digest(host_x),
        "measured_wire_bytes": ledger.measured_wire_bytes,
        "priced_wire_bytes": float(priced),
        "per_link_priced_bytes": float(per_link),
        "round_latency_us": ledger.round_latency_us,
        "ledger": ledger.summary(),
    }
