"""Distributed-runtime tests on 8 forced host devices.

The 8-device forcing must happen before jax initialises, so these tests
run in a subprocess with XLA_FLAGS set (the main test process keeps the
default single device per the assignment).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_ring_mix_matches_dense_mixing_matrix():
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.sharding.collectives import ring_mix_leaf
        from repro.core import ring_mixing

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = ring_mixing(m, self_weight=1/3)
        x = jax.random.normal(jax.random.PRNGKey(0), (m, 16))
        fn = jax.shard_map(lambda t: ring_mix_leaf(t, ("data",), 1/3),
                           mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), axis_names={"data"},
                           check_vma=False)
        with jax.set_mesh(mesh):
            got = jax.jit(fn)(x)
        want = jnp.asarray(spec.matrix, jnp.float32) @ x
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        print("RING_OK")
    """)
    assert "RING_OK" in out


def test_distributed_interact_matches_reference_trajectory():
    """The shard_map/ppermute train step must produce the same iterates as
    a single-host dense-mixing reference implementation of Algorithm 1."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.core import ring_mixing, mix_pytree
        from repro.sharding.partition import tree_shardings
        from repro.train.bilevel_lm import BilevelHyper, local_grads
        from repro.train.step import (InteractConfig, init_train_state,
                                      make_train_step, train_state_specs)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("smollm-360m").reduced(vocab_size=128, num_layers=2,
                                                dtype="float32")
        hyper = BilevelHyper(mu_g=0.5, neumann_k=2, lipschitz_g=4.0,
                             ce_chunk=16, remat=False)
        icfg = InteractConfig(alpha=0.05, beta=0.3, hyper=hyper)
        m = 4
        state = init_train_state(cfg, jax.random.PRNGKey(0), m)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (m, 4, 32), 0,
                                    cfg.vocab_size)

        # ---- distributed trajectory
        dstate = jax.device_put(
            state, tree_shardings(mesh, train_state_specs(state, mesh)))
        dtok = jax.device_put(tokens, NamedSharding(mesh, P("data")))
        step = make_train_step(cfg, mesh, icfg)
        with jax.set_mesh(mesh):
            jstep = jax.jit(step)
            for _ in range(2):
                dstate, _ = jstep(dstate, dtok)

        # ---- reference: dense mixing matrix + per-agent local_grads
        spec = ring_mixing(m, self_weight=icfg.self_weight)
        mat = jnp.asarray(spec.matrix, jnp.float32)
        rstate = state
        for _ in range(2):
            x_mixed = mix_pytree(mat, rstate.x)
            u_mixed = mix_pytree(mat, rstate.u)
            x_new = jax.tree_util.tree_map(
                lambda mx, u: mx - icfg.alpha * u, x_mixed, rstate.u)
            y_new = rstate.y - icfg.beta * rstate.v
            ps, vs = [], []
            for i in range(m):
                xi = jax.tree_util.tree_map(lambda l: l[i], x_new)
                p, v, _ = local_grads(cfg, hyper, xi, y_new[i],
                                      tokens[i, :2], tokens[i, 2:])
                ps.append(p); vs.append(v)
            p_new = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *ps)
            v_new = jnp.stack(vs)
            u_new = jax.tree_util.tree_map(
                lambda mu, pn, pp: mu + pn - pp, u_mixed, p_new,
                rstate.p_prev)
            rstate = rstate._replace(x=x_new, y=y_new, u=u_new, v=v_new,
                                     p_prev=p_new, t=rstate.t + 1)

        for a, b in zip(jax.tree_util.tree_leaves(dstate.x),
                        jax.tree_util.tree_leaves(rstate.x)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-3)
        for a, b in zip(jax.tree_util.tree_leaves(dstate.u),
                        jax.tree_util.tree_leaves(rstate.u)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=5e-3)
        print("TRAJECTORY_OK")
    """)
    assert "TRAJECTORY_OK" in out


def test_dryrun_single_combo_small_mesh():
    """The dry-run machinery end-to-end on a 4x2 mesh with a reduced
    config: lower, compile, memory/cost analysis, collective parse."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.dryrun import parse_collectives
        from repro.launch.serving import make_serve_step
        from repro.models import model as M
        from repro.sharding.partition import cache_specs, tree_specs, tree_shardings

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced(vocab_size=128)
        params_sh = jax.eval_shape(
            lambda k: M.init_params(cfg, k, with_head=True),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        p_shard = tree_shardings(mesh, tree_specs(params_sh, 2))
        cache = jax.eval_shape(lambda: M.init_cache(cfg, batch=8, max_len=64))
        c_shard = tree_shardings(mesh, cache_specs(cache, mesh, 8))
        serve = make_serve_step(cfg)
        jitted = jax.jit(serve, in_shardings=(
            p_shard, NamedSharding(mesh, P("data")), c_shard,
            NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, P("data")), c_shard))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(
                params_sh, jax.ShapeDtypeStruct((8, 1), jnp.int32), cache,
                jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
        from repro.roofline.analysis import normalize_cost_analysis
        cost = normalize_cost_analysis(compiled.cost_analysis())
        assert cost["flops"] > 0
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes > 0
        stats = parse_collectives(compiled.as_text())
        print("DRYRUN_OK", stats["wire_bytes"] >= 0)
    """)
    assert "DRYRUN_OK" in out


def _run_two_process(body: str, devices_per_process: int = 2,
                     timeout: float = 600.0):
    """Spawn TWO coordinator-wired jax processes running ``body`` — a
    real ``jax.distributed`` run (gloo CPU collectives) on localhost,
    configured through the REPRO_* env vars ``initialize_from_env``
    reads (docs/DISTRIBUTED.md)."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    code = textwrap.dedent(body)
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{devices_per_process}")
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        env["REPRO_COORDINATOR"] = f"127.0.0.1:{port}"
        env["REPRO_NUM_PROCESSES"] = "2"
        env["REPRO_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for proc in procs:
            out, err = proc.communicate(timeout=timeout)
            assert proc.returncode == 0, err[-4000:]
            outs.append(out)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
    return outs


def test_two_process_init_and_cross_process_collectives():
    """2 processes x 2 devices: ``initialize_from_env`` brings the gloo
    runtime up, the 4-agent mesh spans both processes, and psum/ppermute
    inside shard_map agree with the host-side reference — collectives
    really cross the process boundary (each process only holds half the
    agents)."""
    outs = _run_two_process("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch import distributed as D

        assert D.initialize_from_env()
        assert jax.process_count() == 2
        assert jax.device_count() == 4
        mesh = D.agent_mesh(4)
        host = np.arange(8, dtype=np.float32).reshape(4, 2) + 1.0
        x = D.shard_host_tree(mesh, host, 4)
        gather = D._make_gather(mesh)

        fn = jax.shard_map(lambda t: jax.lax.psum(t, "data"), mesh=mesh,
                           in_specs=(P("data"),), out_specs=P(),
                           axis_names=set(mesh.axis_names), check_vma=False)
        with jax.set_mesh(mesh):
            got = np.asarray(jax.device_get(jax.jit(fn)(x)))
        np.testing.assert_allclose(got, host.sum(axis=0, keepdims=True),
                                   atol=1e-6)

        perm = [(i, (i + 1) % 4) for i in range(4)]
        fn2 = jax.shard_map(lambda t: jax.lax.ppermute(t, "data", perm),
                            mesh=mesh, in_specs=(P("data"),),
                            out_specs=P("data"),
                            axis_names=set(mesh.axis_names), check_vma=False)
        with jax.set_mesh(mesh):
            got2 = np.asarray(gather(jax.jit(fn2)(x)))
        np.testing.assert_allclose(got2, np.roll(host, 1, axis=0),
                                   atol=1e-6)
        D.shutdown()
        print("TWO_PROC_OK", jax.process_index())
    """)
    assert all("TWO_PROC_OK" in out for out in outs)


def test_agent_mesh_divisibility_error_is_actionable():
    out = run_in_subprocess("""
        from repro.launch.distributed import agent_mesh
        try:
            agent_mesh(3)     # 3 does not divide the 8 forced devices
            raise SystemExit("expected ValueError")
        except ValueError as e:
            msg = str(e)
            assert "does not divide" in msg, msg
            assert "divisors" in msg, msg
            assert "--devices-per-process" in msg, msg
        mesh = agent_mesh(4)  # 4 agents x 2 model shards
        assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2
        print("AGENT_MESH_OK")
    """)
    assert "AGENT_MESH_OK" in out


def test_launch_local_end_to_end():
    """The localhost driver end to end: 2 processes x 2 devices through
    run_section6, result JSON carries the measured-communication
    read-out and a finite stationarity metric."""
    import json
    import tempfile
    out_path = os.path.join(tempfile.mkdtemp(prefix="launch_test_"),
                            "result.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "launch_local.py"),
         "--processes", "2", "--devices-per-process", "2",
         "--agents", "4", "--steps", "4", "--record-every", "4",
         "--n-per-agent", "24", "--metric-inner-steps", "20",
         "--out", out_path],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    with open(out_path) as fh:
        res = json.load(fh)
    assert res["num_processes"] == 2
    assert res["num_devices"] == 4
    assert res["num_agents"] == 4
    import math
    assert math.isfinite(res["final_metric"])
    assert res["measured_wire_bytes"] == res["priced_wire_bytes"]
    assert res["round_latency_us"] > 0
    assert len(res["digest"]) == 64     # sha256 hex of the final iterates


def test_multipod_mesh_shapes():
    out = run_in_subprocess("""
        import os
        # simulate enough devices for shape checks only (8 < 512: expect error)
        from repro.launch.mesh import make_production_mesh
        try:
            make_production_mesh()
        except RuntimeError as e:
            assert "512" in str(e) or "256" in str(e) or "devices" in str(e)
            print("MESH_GUARD_OK")
    """)
    assert "MESH_GUARD_OK" in out


def test_agents_per_pod_mode():
    """P6 layout: shard_map over 'pod' only, state FSDP-sharded over data,
    trajectory finite and consensus active across the 2 pod-agents."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.sharding.partition import tree_shardings
        from repro.train.bilevel_lm import BilevelHyper
        from repro.train.step import (InteractConfig, init_train_state,
                                      make_train_step, train_state_specs)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("smollm-360m").reduced(vocab_size=128, num_layers=2,
                                                dtype="float32")
        hyper = BilevelHyper(mu_g=0.5, neumann_k=2, lipschitz_g=4.0,
                             ce_chunk=16, remat=False, batch_shard=True)
        icfg = InteractConfig(alpha=0.05, beta=0.3, hyper=hyper)
        m = 2  # agents = pods
        state = init_train_state(cfg, jax.random.PRNGKey(0), m)
        specs = train_state_specs(state, mesh, agent_mode="pods")
        # layer leaves must be sharded over data too (FSDP)
        layer_specs = jax.tree_util.tree_leaves(
            specs.x["layers"], is_leaf=lambda x: isinstance(x, P))
        assert any("data" in str(sp) for sp in layer_specs), layer_specs
        dstate = jax.device_put(state, tree_shardings(mesh, specs))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (m, 4, 32), 0,
                                    cfg.vocab_size)
        dtok = jax.device_put(tokens, NamedSharding(mesh, P("pod")))
        step = make_train_step(cfg, mesh, icfg, agent_mode="pods")
        with jax.set_mesh(mesh):
            jstep = jax.jit(step)
            for _ in range(2):
                dstate, metrics = jstep(dstate, dtok)
        leaf = jax.tree_util.tree_leaves(dstate.x)[0]
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
        assert bool(jnp.isfinite(metrics["outer_ce"]))
        print("PODS_OK", float(metrics["outer_ce"]))
    """)
    assert "PODS_OK" in out


def test_distributed_svr_interact_runs():
    """Distributed SVR-INTERACT: finite trajectory, refresh cadence, and
    agreement with INTERACT on refresh steps (same full-gradient math)."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.sharding.partition import tree_shardings
        from repro.train.bilevel_lm import BilevelHyper
        from repro.train.step import InteractConfig
        from repro.train.svr_step import (init_svr_train_state,
                                          make_svr_train_step,
                                          svr_train_state_specs)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("smollm-360m").reduced(vocab_size=128, num_layers=2,
                                                dtype="float32")
        hyper = BilevelHyper(mu_g=0.5, neumann_k=2, lipschitz_g=4.0,
                             ce_chunk=16, remat=False)
        icfg = InteractConfig(alpha=0.05, beta=0.3, hyper=hyper)
        m = 4
        state = init_svr_train_state(cfg, jax.random.PRNGKey(0), m)
        specs = svr_train_state_specs(state, mesh)
        state = jax.device_put(state, tree_shardings(mesh, specs))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (m, 4, 32), 0,
                                    cfg.vocab_size)
        tokens = jax.device_put(tokens, NamedSharding(mesh, P("data")))
        step = make_svr_train_step(cfg, mesh, icfg, q=3)
        with jax.set_mesh(mesh):
            jstep = jax.jit(step)
            refreshes = []
            for _ in range(4):
                state, metrics = jstep(state, tokens)
                refreshes.append(float(metrics["refresh"]))
                assert bool(jnp.isfinite(metrics["outer_ce"]))
        assert refreshes == [0.0, 0.0, 1.0, 0.0]  # t=1,2,3,4 with q=3
        leaf = jax.tree_util.tree_leaves(state.x)[0]
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
        print("SVR_DIST_OK")
    """)
    assert "SVR_DIST_OK" in out


def test_compressed_and_dp_consensus():
    """Paper future-work hooks: int8-compressed and DP-noised consensus
    still drive the trajectory (bounded perturbation, tracking absorbs)."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.sharding.collectives import (ring_mix_leaf, quantize_int8,
                                                dequantize_int8)
        from repro.core import ring_mixing

        # quantize/dequantize round-trip error bounded by scale/2
        x = jax.random.normal(jax.random.PRNGKey(0), (64,)) * 3.0
        q, s = quantize_int8(x)
        err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
        assert float(err) <= float(s) * 0.5 + 1e-6

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = ring_mixing(m, self_weight=1/3)
        X = jax.random.normal(jax.random.PRNGKey(1), (m, 32))

        def run(**kw):
            fn = jax.shard_map(
                lambda t: ring_mix_leaf(t, ("data",), 1/3, **kw),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                axis_names={"data"}, check_vma=False)
            with jax.set_mesh(mesh):
                return jax.jit(fn)(X)

        exact = jnp.asarray(spec.matrix, jnp.float32) @ X
        got_q = run(compress="int8")
        # int8 error small relative to payload magnitude
        rel = float(jnp.max(jnp.abs(got_q - exact))) / float(jnp.max(jnp.abs(exact)))
        assert rel < 0.05, rel

        got_dp = run(dp_sigma=0.1, dp_key=jax.random.PRNGKey(2))
        # noised but unbiased-ish: distinct from exact yet close
        d = float(jnp.max(jnp.abs(got_dp - exact)))
        assert 0.0 < d < 1.0, d
        print("COMPRESS_DP_OK")
    """)
    assert "COMPRESS_DP_OK" in out
