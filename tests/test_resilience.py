"""Fault-tolerance tests (docs/RESILIENCE.md): bitwise kill/resume for
every registry solver, the chaos fault harness, and self-healing sweeps.

The bitwise contract under test: a run killed at an arbitrary step and
resumed from its newest valid snapshot reproduces the uninterrupted
``run_traced`` metric trace bit for bit — dense backend in-process, the
ppermute backend through the distributed train step in an 8-device
subprocess (ppermute is mesh-native; it only runs under shard_map).
"""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import numpy as np
import pytest

from repro.consensus import CompressionConfig
from repro.core import (
    HypergradConfig,
    MLPMetaProblem,
    convergence_metric_fn,
    erdos_renyi_adjacency,
    init_head,
    init_mlp_backbone,
    laplacian_mixing,
    make_synthetic_agents,
)
from repro.resilience import (
    FaultPlan,
    NonFiniteStateError,
    SimulatedKill,
    available_faults,
    chaos_run,
    make_fault,
    register_fault,
    resume,
    resume_run,
    run_resumable,
    snapshot,
)
from repro.solvers import SolverConfig, available_solvers, make_solver, sweep

M, N, BATCH, Q, SEED = 4, 60, 6, 5, 7
ITERS, REC = 12, 3
CKPT_EVERY = 5          # co-prime with REC: boundaries never align
KILL_AT = 7             # mid-chunk — the hard case

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    data = make_synthetic_agents(key, num_agents=M, n_per_agent=N,
                                 d_in=8, num_classes=3)
    prob = MLPMetaProblem(mu_g=0.5, lipschitz_g=4.0)
    x0 = init_mlp_backbone(jax.random.PRNGKey(1), 8, hidden=8)
    y0 = init_head(jax.random.PRNGKey(2), 8, 3)
    spec = laplacian_mixing(erdos_renyi_adjacency(M, 0.5, seed=3))
    hg = HypergradConfig(method="cg", cg_iters=8)
    metric = convergence_metric_fn(prob, hg, data, inner_steps=40)
    return data, prob, x0, y0, spec, hg, metric


def _config(setup, algo, **overrides):
    _, _, _, _, spec, hg, _ = setup
    kw = dict(algo=algo, alpha=0.3, beta=0.3, batch_size=BATCH, q=Q,
              mixing=spec, hypergrad=hg, seed=SEED)
    kw.update(overrides)
    return SolverConfig(**kw)


def _fresh(setup, cfg):
    data, prob, x0, y0, _, _, _ = setup
    solver = make_solver(cfg)
    return solver, solver.init(None, prob, None, x0, y0, data)


def _ref_trace(setup, cfg):
    data, _, _, _, _, _, metric = setup
    solver, state = _fresh(setup, cfg)
    _, ref = solver.run_traced(state, data, ITERS, REC, metric)
    return np.asarray(jax.device_get(ref))


def _kill_then_resume(setup, cfg):
    """Kill at KILL_AT, resume from disk, return the stitched trace."""
    data, prob, x0, y0, _, _, metric = setup
    with tempfile.TemporaryDirectory() as ckpt:
        plan = FaultPlan([make_fault("kill", step=KILL_AT)], seed=0)
        solver, state = _fresh(setup, cfg)
        with pytest.raises(SimulatedKill):
            run_resumable(solver, state, data, ITERS, REC, metric,
                          checkpoint_every=CKPT_EVERY, ckpt_dir=ckpt,
                          hooks=plan)
        # the kill landed at boundary 10, before its snapshot: only the
        # step-5 checkpoint may exist, so steps 5..12 get replayed
        rs = resume(cfg, ckpt, problem=prob, x0=x0, y0=y0, data=data)
        assert rs is not None and rs.step == CKPT_EVERY
        _, _, trace = resume_run(cfg, ckpt, ITERS, REC, metric,
                                 checkpoint_every=CKPT_EVERY,
                                 problem=prob, x0=x0, y0=y0, data=data)
    return np.asarray(trace)


@pytest.mark.parametrize("algo", sorted(available_solvers()))
def test_kill_resume_bitwise_dense(setup, algo):
    cfg = _config(setup, algo)
    ref = _ref_trace(setup, cfg)
    trace = _kill_then_resume(setup, cfg)
    assert trace.dtype == ref.dtype and trace.shape == ref.shape
    assert trace.tobytes() == ref.tobytes()


def test_kill_resume_bitwise_compressed_ef(setup):
    """The EF wire state {e, ref} rides in the carry: resume must
    restore it or the compressed trajectory forks."""
    cfg = _config(setup, "interact",
                  compression=CompressionConfig(kind="sign1bit",
                                                error_feedback=True))
    ref = _ref_trace(setup, cfg)
    trace = _kill_then_resume(setup, cfg)
    assert trace.tobytes() == ref.tobytes()


def test_ppermute_checkpoint_resume_bitwise():
    """ppermute parity runs through the distributed train step (the one
    end-to-end ppermute path; the engine requires shard_map), in a
    subprocess with 8 forced host devices: 4 uninterrupted steps vs
    2 steps -> checkpoint round-trip -> 2 steps must match bitwise."""
    code = textwrap.dedent("""
        import tempfile
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import latest_step, restore_step, save_step
        from repro.configs import get_config
        from repro.sharding.partition import tree_shardings
        from repro.train.bilevel_lm import BilevelHyper
        from repro.train.step import (InteractConfig, init_train_state,
                                      make_train_step, train_state_specs)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("smollm-360m").reduced(
            vocab_size=128, num_layers=2, dtype="float32")
        hyper = BilevelHyper(mu_g=0.5, neumann_k=2, lipschitz_g=4.0,
                             ce_chunk=16, remat=False)
        icfg = InteractConfig(alpha=0.05, beta=0.3, hyper=hyper)
        m = 4
        state0 = init_train_state(cfg, jax.random.PRNGKey(0), m)
        shards = tree_shardings(mesh, train_state_specs(state0, mesh))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (m, 4, 32), 0,
                                    cfg.vocab_size)
        step = make_train_step(cfg, mesh, icfg)

        def advance(state, n):
            dstate = jax.device_put(state, shards)
            dtok = jax.device_put(tokens, NamedSharding(mesh, P("data")))
            with jax.set_mesh(mesh):
                jstep = jax.jit(step)
                for _ in range(n):
                    dstate, _ = jstep(dstate, dtok)
            return jax.device_get(dstate)

        ref = advance(state0, 4)
        with tempfile.TemporaryDirectory() as d:
            mid = advance(state0, 2)
            save_step(d, 2, mid)
            assert latest_step(d) == 2
            restored = restore_step(d, 2, mid)
            got = advance(restored, 2)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        print("PPERMUTE_RESUME_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PPERMUTE_RESUME_OK" in proc.stdout


# -- snapshot/resume edge cases -----------------------------------------


def test_resume_skips_corrupt_newest_snapshot(setup):
    data, prob, x0, y0, _, _, metric = setup
    cfg = _config(setup, "interact")
    solver, state = _fresh(setup, cfg)
    with tempfile.TemporaryDirectory() as ckpt:
        run_resumable(solver, state, data, ITERS, REC, metric,
                      checkpoint_every=CKPT_EVERY, ckpt_dir=ckpt)
        # snapshots at 5, 10, 12; damage the newest archive
        newest = os.path.join(ckpt, f"step_{ITERS:08d}.npz")
        size = os.path.getsize(newest)
        with open(newest, "r+b") as fh:
            fh.truncate(size // 3)
        rs = resume(cfg, ckpt, problem=prob, x0=x0, y0=y0, data=data)
        assert rs is not None
        assert rs.step == 10   # newest *valid* snapshot
        assert int(np.asarray(rs.state.t)) == 10


def test_resume_refuses_wrong_config(setup):
    data, prob, x0, y0, _, _, _ = setup
    cfg = _config(setup, "interact")
    solver, state = _fresh(setup, cfg)
    with tempfile.TemporaryDirectory() as ckpt:
        run_resumable(solver, state, data, CKPT_EVERY,
                      checkpoint_every=CKPT_EVERY, ckpt_dir=ckpt)
        other = _config(setup, "interact", alpha=0.11)
        with pytest.raises(ValueError, match="different config"):
            resume(other, ckpt, problem=prob, x0=x0, y0=y0, data=data)
        assert resume(other, ckpt, problem=prob, x0=x0, y0=y0,
                      data=data, strict=False) is None


def test_resume_empty_dir(setup):
    data, prob, x0, y0, _, _, _ = setup
    cfg = _config(setup, "interact")
    with tempfile.TemporaryDirectory() as ckpt:
        assert resume(cfg, ckpt, problem=prob, x0=x0, y0=y0,
                      data=data) is None
        with pytest.raises(ValueError, match="num_steps"):
            resume_run(cfg, ckpt, checkpoint_every=CKPT_EVERY,
                       problem=prob, x0=x0, y0=y0, data=data)


def test_nan_payload_detected_before_snapshot(setup):
    """A poisoned chunk must raise and must NOT land on disk."""
    data, _, _, _, _, _, metric = setup
    cfg = _config(setup, "interact")
    solver, state = _fresh(setup, cfg)
    plan = FaultPlan([make_fault("nan-payload", step=2)], seed=0)
    with tempfile.TemporaryDirectory() as ckpt:
        with pytest.raises(NonFiniteStateError):
            run_resumable(solver, state, data, ITERS, REC, metric,
                          checkpoint_every=CKPT_EVERY, ckpt_dir=ckpt,
                          hooks=plan)
        assert not [f for f in os.listdir(ckpt) if f.endswith(".npz")]


def test_write_failure_absorbed_by_snapshot_retry(setup):
    data, _, _, _, _, _, metric = setup
    cfg = _config(setup, "interact")
    solver, state = _fresh(setup, cfg)
    plan = FaultPlan([make_fault("write-failure", step=0, count=2)],
                     seed=0)
    with tempfile.TemporaryDirectory() as ckpt:
        run_resumable(solver, state, data, CKPT_EVERY, REC, metric,
                      checkpoint_every=CKPT_EVERY, ckpt_dir=ckpt,
                      hooks=plan, backoff=0.001)
        assert plan.count("write-failure") == 2   # retried, then landed
        assert os.path.exists(
            os.path.join(ckpt, f"step_{CKPT_EVERY:08d}.npz"))


def test_write_failure_beyond_retry_budget_raises(setup):
    data, _, _, _, _, _, metric = setup
    cfg = _config(setup, "interact")
    solver, state = _fresh(setup, cfg)
    plan = FaultPlan([make_fault("write-failure", step=0, count=10)],
                     seed=0)
    with tempfile.TemporaryDirectory() as ckpt:
        with pytest.raises(OSError):
            run_resumable(solver, state, data, CKPT_EVERY, REC, metric,
                          checkpoint_every=CKPT_EVERY, ckpt_dir=ckpt,
                          hooks=plan, retries=2, backoff=0.001)


def test_fault_registry():
    kinds = available_faults()
    for kind in ("kill", "nan-payload", "corrupt-checkpoint",
                 "stale-checkpoint", "write-failure"):
        assert kind in kinds
    with pytest.raises(ValueError, match="unknown fault"):
        make_fault("fsck")
    with pytest.raises(ValueError, match="already registered"):
        register_fault("kill")(type("Impostor", (), {}))


def test_fault_plan_reset_rearms():
    plan = FaultPlan([make_fault("kill", step=3),
                      make_fault("write-failure", step=0, count=2)],
                     seed=0)
    with pytest.raises(SimulatedKill):
        plan.on_chunk_end(0, 5, None, 10)
    assert plan.on_chunk_end(5, 10, None, 10) is None   # one-shot
    plan.reset()
    assert plan.events == []
    with pytest.raises(SimulatedKill):
        plan.on_chunk_end(0, 5, None, 10)


# -- chaos campaign ------------------------------------------------------


def test_chaos_campaign_completes_bitwise(setup):
    data, prob, x0, y0, _, _, metric = setup
    cfg = _config(setup, "interact")
    ref = _ref_trace(setup, cfg)
    plan = FaultPlan([
        make_fault("kill", step=3),
        make_fault("kill", step=6),
        make_fault("kill", step=9),
        make_fault("nan-payload", step=4),
        make_fault("corrupt-checkpoint", step=6, mode="garbage"),
        make_fault("stale-checkpoint", step=8),
        make_fault("write-failure", step=3, count=2),
    ], seed=1)
    with tempfile.TemporaryDirectory() as ckpt:
        rep = chaos_run(cfg, plan, ITERS, REC,
                        checkpoint_every=CKPT_EVERY, ckpt_dir=ckpt,
                        metric_fn=metric, problem=prob, x0=x0, y0=y0,
                        data=data, backoff=0.001)
    assert rep.completed
    assert rep.kills >= 3
    assert rep.restarts >= 3
    assert rep.nonfinite_faults >= 1
    assert rep.write_retries >= 2
    assert rep.wasted_steps > 0
    assert rep.trace is not None and rep.trace.tobytes() == ref.tobytes()
    assert np.isclose(rep.final_metric, float(ref[-1]),
                      rtol=1e-6, atol=1e-9)


# -- self-healing sweeps -------------------------------------------------


def _sweep_grid(setup):
    return [_config(setup, "interact", alpha=0.2),
            _config(setup, "interact", alpha=0.3),
            _config(setup, "gt-dsgd")]


def test_sweep_resume_recomputes_only_missing_groups(setup):
    data, prob, x0, y0, _, _, metric = setup
    grid = _sweep_grid(setup)
    kw = dict(problem=prob, x0=x0, y0=y0, data=data, metric_fn=metric)
    clean = sweep(grid, ITERS, REC, **kw)
    with tempfile.TemporaryDirectory() as d:
        # mid-grid failure: only the interact group ever completed
        partial = sweep(grid[:2], ITERS, REC, resume_dir=d, **kw)
        assert [g.loaded for g in partial.groups] == [False]
        assert os.path.exists(os.path.join(d, "manifest.json"))
        full = sweep(grid, ITERS, REC, resume_dir=d, **kw)
        assert [g.loaded for g in full.groups] == [True, False]
        again = sweep(grid, ITERS, REC, resume_dir=d, **kw)
        assert [g.loaded for g in again.groups] == [True, True]
    assert full.traces.tobytes() == clean.traces.tobytes()
    assert again.traces.tobytes() == clean.traces.tobytes()


def test_sweep_resume_ignores_foreign_geometry(setup):
    """A manifest written for different sweep geometry must not be
    loaded — every group recomputes under the new fingerprint."""
    data, prob, x0, y0, _, _, metric = setup
    grid = _sweep_grid(setup)[:2]
    kw = dict(problem=prob, x0=x0, y0=y0, data=data, metric_fn=metric)
    with tempfile.TemporaryDirectory() as d:
        sweep(grid, ITERS, REC, resume_dir=d, **kw)
        other = sweep(grid, ITERS + REC, REC, resume_dir=d, **kw)
        assert [g.loaded for g in other.groups] == [False]


def test_sweep_resume_rejects_return_states(setup):
    data, prob, x0, y0, _, _, _ = setup
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="return_states"):
            sweep(_sweep_grid(setup)[:1], ITERS, 0, problem=prob, x0=x0,
                  y0=y0, data=data, return_states=True, resume_dir=d)


# -- snapshot internals --------------------------------------------------


def test_snapshot_meta_and_padded_roundtrip(setup):
    data, prob, x0, y0, _, _, _ = setup
    cfg = _config(setup, "interact")
    solver, state = _fresh(setup, cfg)
    padded = np.full((ITERS,), np.nan, np.float32)
    padded[:4] = np.arange(4, dtype=np.float32)
    with tempfile.TemporaryDirectory() as ckpt:
        snapshot(solver, state, 0, ckpt, padded=padded,
                 total_steps=ITERS, record_every=REC)
        rs = resume(cfg, ckpt, problem=prob, x0=x0, y0=y0, data=data)
    assert rs.total_steps == ITERS and rs.record_every == REC
    assert rs.padded.tobytes() == padded.tobytes()
    assert rs.meta["algo"] == "interact"
