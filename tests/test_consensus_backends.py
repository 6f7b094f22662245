"""Cross-backend equivalence of the ConsensusEngine API.

dense (matmul reference), pallas (fused kernel, interpret mode), and
ppermute (shard_map collectives on 8 forced host devices) must produce
identical mixed trees — for the ring AND the paper's Section-6
Erdős–Rényi topology (the latter previously impossible on the
distributed path) and a torus — and one full ``interact_step`` must
agree across the single-host backends.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.consensus import DenseEngine, PallasEngine, as_engine, make_engine
from repro.core import (
    erdos_renyi_adjacency, laplacian_mixing, mix_pytree, ring_mixing,
    torus_mixing)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

M_AGENTS = 8


def _specs():
    return {
        "ring": ring_mixing(M_AGENTS, self_weight=1.0 / 3.0),
        "erdos-renyi": laplacian_mixing(
            erdos_renyi_adjacency(M_AGENTS, 0.5, seed=11)),
        "torus": torus_mixing(2, 4),
    }


def _tree(key, m=M_AGENTS):
    """Leaf sizes chosen so the flattened D is NOT a block_d multiple."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (m, 37, 5)),
        "b": jax.random.normal(k2, (m, 131)),
        "nest": (jax.random.normal(k3, (m, 3)),),
    }


@pytest.mark.parametrize("topology", ["ring", "erdos-renyi", "torus"])
def test_dense_and_pallas_mix_agree(topology):
    spec = _specs()[topology]
    tree = _tree(jax.random.PRNGKey(0))
    dense = DenseEngine(spec)
    pallas = PallasEngine(spec, interpret=True)
    md, mp = dense.mix(tree), pallas.mix(tree)
    ref = mix_pytree(jnp.asarray(spec.matrix), tree)
    for a, b, r in zip(jax.tree_util.tree_leaves(md),
                       jax.tree_util.tree_leaves(mp),
                       jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=1e-5)
        np.testing.assert_allclose(np.asarray(b), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("topology", ["ring", "erdos-renyi"])
def test_dense_and_pallas_fused_step_agree(topology):
    spec = _specs()[topology]
    key = jax.random.PRNGKey(1)
    x = _tree(key)
    u = jax.tree_util.tree_map(lambda l: 0.5 * l, x)
    p = jax.tree_util.tree_map(lambda l: 0.1 * l, x)
    pp = jax.tree_util.tree_map(lambda l: 0.2 * l, x)
    xd, ud = DenseEngine(spec).step1_step3(x, u, p, pp, 0.3)
    xp, up = PallasEngine(spec).step1_step3(x, u, p, pp, 0.3)
    for a, b in zip(jax.tree_util.tree_leaves(xd),
                    jax.tree_util.tree_leaves(xp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ud),
                    jax.tree_util.tree_leaves(up)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_as_engine_coerces_matrix():
    spec = _specs()["ring"]
    tree = _tree(jax.random.PRNGKey(2))
    got = as_engine(jnp.asarray(spec.matrix)).mix(tree)
    want = mix_pytree(jnp.asarray(spec.matrix), tree)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown consensus backend"):
        make_engine("carrier-pigeon", _specs()["ring"])


def test_full_interact_step_agrees_across_backends():
    """One full Algorithm-1 trajectory: dense vs pallas backends."""
    from repro.core import (
        HypergradConfig, MLPMetaProblem, init_head, init_mlp_backbone,
        init_state, make_interact_step, make_synthetic_agents)
    m = 5
    data = make_synthetic_agents(jax.random.PRNGKey(0), num_agents=m,
                                 n_per_agent=60, d_in=8, num_classes=3)
    prob = MLPMetaProblem(mu_g=0.5, lipschitz_g=4.0)
    x0 = init_mlp_backbone(jax.random.PRNGKey(1), 8, hidden=10)
    y0 = init_head(jax.random.PRNGKey(2), 10, 3)
    spec = laplacian_mixing(erdos_renyi_adjacency(m, 0.6, seed=3))
    hg = HypergradConfig(method="cg", cg_iters=16)

    # two independent (identical) states: the solver step closures donate
    # their input buffers, so the trajectories must not share storage
    st_d = init_state(prob, hg, x0, y0, data)
    st_p = init_state(prob, hg, x0, y0, data)
    step_d = make_interact_step(prob, hg, spec, 0.3, 0.3, backend="dense")
    step_p = make_interact_step(prob, hg, spec, 0.3, 0.3, backend="pallas")
    for _ in range(3):
        st_d = step_d(st_d, data)
        st_p = step_p(st_p, data)
    for a, b in zip(jax.tree_util.tree_leaves(st_d.x),
                    jax.tree_util.tree_leaves(st_p.x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
    for a, b in zip(jax.tree_util.tree_leaves(st_d.u),
                    jax.tree_util.tree_leaves(st_p.u)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def run_in_subprocess(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_ppermute_backend_matches_dense_all_topologies():
    """The distributed backend (shard_map on 8 forced host devices)
    reproduces the dense mixed trees for ring, ER, and torus graphs, and
    the fused step1_step3 agrees too."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.consensus import DenseEngine, PermuteEngine
        from repro.core import (erdos_renyi_adjacency, laplacian_mixing,
                                ring_mixing, torus_mixing)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        specs = {
            "ring": ring_mixing(m, self_weight=1/3),
            "erdos-renyi": laplacian_mixing(
                erdos_renyi_adjacency(m, 0.5, seed=11)),
            "torus": torus_mixing(2, 4),
        }
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (m, 37, 5)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (m, 131))}
        u = jax.tree_util.tree_map(lambda l: 0.5 * l, tree)
        p = jax.tree_util.tree_map(lambda l: 0.1 * l, tree)
        pp = jax.tree_util.tree_map(lambda l: 0.2 * l, tree)
        for name, spec in specs.items():
            eng = PermuteEngine(spec, agent_axes=("data",))
            dense = DenseEngine(spec)
            fn = jax.shard_map(lambda t: eng.mix(t), mesh=mesh,
                               in_specs=P("data"), out_specs=P("data"),
                               axis_names={"data"}, check_vma=False)
            with jax.set_mesh(mesh):
                got = jax.jit(fn)(tree)
            want = dense.mix(tree)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            fused = jax.shard_map(
                lambda x_, u_, p_, pp_: eng.step1_step3(x_, u_, p_, pp_,
                                                        0.3),
                mesh=mesh, in_specs=(P("data"),) * 4,
                out_specs=(P("data"), P("data")), axis_names={"data"},
                check_vma=False)
            with jax.set_mesh(mesh):
                xg, ug = jax.jit(fused)(tree, u, p, pp)
            xd, ud = dense.step1_step3(tree, u, p, pp, 0.3)
            for a, b in zip(jax.tree_util.tree_leaves(xg),
                            jax.tree_util.tree_leaves(xd)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            for a, b in zip(jax.tree_util.tree_leaves(ug),
                            jax.tree_util.tree_leaves(ud)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            print(name, "OK", eng.rounds_per_mix)
        print("BACKENDS_OK")
    """)
    assert "BACKENDS_OK" in out


def test_allgather_backend_matches_dense_all_topologies():
    """The mesh dense-matmul backend (all_gather inside shard_map on 8
    forced host devices) reproduces the dense mixed trees for ring, ER,
    and torus graphs, agrees on the fused step1_step3, and runs the
    robust (trimmed) combine exactly like the dense reference — the
    property ppermute cannot offer (no all-to-all access)."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.consensus import AllGatherEngine, DenseEngine
        from repro.core import (erdos_renyi_adjacency, laplacian_mixing,
                                ring_mixing, torus_mixing)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        specs = {
            "ring": ring_mixing(m, self_weight=1/3),
            "erdos-renyi": laplacian_mixing(
                erdos_renyi_adjacency(m, 0.5, seed=11)),
            "torus": torus_mixing(2, 4),
        }
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (m, 37, 5)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (m, 131))}
        u = jax.tree_util.tree_map(lambda l: 0.5 * l, tree)
        p = jax.tree_util.tree_map(lambda l: 0.1 * l, tree)
        pp = jax.tree_util.tree_map(lambda l: 0.2 * l, tree)
        for name, spec in specs.items():
            eng = AllGatherEngine(spec, agent_axes=("data",))
            dense = DenseEngine(spec)
            fn = jax.shard_map(lambda t: eng.mix(t), mesh=mesh,
                               in_specs=P("data"), out_specs=P("data"),
                               axis_names={"data"}, check_vma=False)
            with jax.set_mesh(mesh):
                got = jax.jit(fn)(tree)
            want = dense.mix(tree)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            fused = jax.shard_map(
                lambda x_, u_, p_, pp_: eng.step1_step3(x_, u_, p_, pp_,
                                                        0.3),
                mesh=mesh, in_specs=(P("data"),) * 4,
                out_specs=(P("data"), P("data")), axis_names={"data"},
                check_vma=False)
            with jax.set_mesh(mesh):
                xg, ug = jax.jit(fused)(tree, u, p, pp)
            xd, ud = dense.step1_step3(tree, u, p, pp, 0.3)
            for a, b in zip(jax.tree_util.tree_leaves(xg),
                            jax.tree_util.tree_leaves(xd)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            for a, b in zip(jax.tree_util.tree_leaves(ug),
                            jax.tree_util.tree_leaves(ud)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            print(name, "OK")

        # robust combine: the gathered table gives all-to-all access, so
        # trimmed-mean must match the dense backend's exactly
        from repro.byzantine import ByzantineConfig
        byz = ByzantineConfig(combine="trimmed-mean")
        spec = specs["erdos-renyi"]
        engr = AllGatherEngine(spec, agent_axes=("data",), byzantine=byz)
        fn = jax.shard_map(lambda t: engr._combine(t), mesh=mesh,
                           in_specs=P("data"), out_specs=P("data"),
                           axis_names={"data"}, check_vma=False)
        with jax.set_mesh(mesh):
            got = jax.jit(fn)(tree)
        want = DenseEngine(spec, byzantine=byz)._combine(tree)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        print("ALLGATHER_OK")
    """)
    assert "ALLGATHER_OK" in out


def test_allgather_compressed_mix_ef_matches_dense_bitwise():
    """int8+EF through the allgather backend: the wire math is the base
    (dense) implementation verbatim — one concatenated per-agent buffer
    — so under shard_map the mixed tree AND the EF state must match the
    dense backend exactly, not just within quantization tolerance."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.consensus import (AllGatherEngine, CompressionConfig,
                                     DenseEngine)
        from repro.core import erdos_renyi_adjacency, laplacian_mixing

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = laplacian_mixing(erdos_renyi_adjacency(m, 0.5, seed=11))
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (m, 37, 5)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (m, 131))}
        zeros = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, jnp.float32), tree)
        ef = {"e": zeros, "ref": zeros}
        comp = CompressionConfig("int8")
        t0 = jnp.zeros((), jnp.int32)

        md, efd = DenseEngine(spec, compression=comp).mix_ef(tree, ef, t0)
        eng = AllGatherEngine(spec, agent_axes=("data",), compression=comp)
        fn = jax.shard_map(lambda t, r: eng.mix_ef(t, r, t0), mesh=mesh,
                           in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")),
                           axis_names={"data"}, check_vma=False)
        with jax.set_mesh(mesh):
            mg, efg = jax.jit(fn)(tree, ef)
        for a, b in zip(jax.tree_util.tree_leaves(mg),
                        jax.tree_util.tree_leaves(md)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(efg),
                        jax.tree_util.tree_leaves(efd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        print("ALLGATHER_EF_OK")
    """)
    assert "ALLGATHER_EF_OK" in out


def test_consensus_step_preserves_mixed_dtypes():
    """The fused op must not cast the tracker to x's leaf dtypes."""
    from repro.kernels.consensus_step import ops as cs_ops
    spec = _specs()["ring"]
    mix = jnp.asarray(spec.matrix, jnp.float32)
    m = M_AGENTS
    x = {"a": jnp.ones((m, 33), jnp.bfloat16), "b": jnp.ones((m, 7))}
    u = {"a": jnp.ones((m, 33)), "b": jnp.ones((m, 7))}
    x_new, u_new = cs_ops.consensus_step(mix, x, u, u, u, alpha=0.1)
    assert x_new["a"].dtype == jnp.bfloat16
    assert u_new["a"].dtype == jnp.float32   # u keeps its own dtype
    assert u_new["b"].dtype == jnp.float32


def test_dp_noise_independent_across_leaves():
    """Same-shaped leaves must get independent DP noise, otherwise a
    neighbour could difference two leaves and cancel the noise."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import ring_mixing
        from repro.sharding.collectives import ring_mix_tree

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = ring_mixing(m, self_weight=1/3)
        leaf = jax.random.normal(jax.random.PRNGKey(0), (m, 32))
        tree = {"a": leaf, "b": leaf}     # identical same-shaped leaves
        fn = jax.shard_map(
            lambda t: ring_mix_tree(t, ("data",), 1/3, dp_sigma=0.1,
                                    dp_key=jax.random.PRNGKey(3)),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            axis_names={"data"}, check_vma=False)
        with jax.set_mesh(mesh):
            got = jax.jit(fn)(tree)
        # identical inputs + identical noise would give identical outputs;
        # independent per-leaf noise must make them differ
        d = float(jnp.max(jnp.abs(got["a"] - got["b"])))
        assert d > 1e-4, d
        print("DP_LEAVES_OK", d)
    """)
    assert "DP_LEAVES_OK" in out


def test_psum_impl_matches_ppermute_impl():
    """The all-reduce fallback (partial-auto old-JAX bodies) is the same
    mixing matrix — identical results, including int8 compression."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.consensus import PermuteEngine
        from repro.core import erdos_renyi_adjacency, laplacian_mixing

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = laplacian_mixing(erdos_renyi_adjacency(m, 0.5, seed=4))
        X = jax.random.normal(jax.random.PRNGKey(0), (m, 64))
        ids = jnp.arange(m, dtype=jnp.int32)
        for compress in (None, "int8"):
            outs = []
            for impl in ("ppermute", "psum"):
                eng = PermuteEngine(spec, agent_axes=("data",),
                                    compress=compress, impl=impl)
                fn = jax.shard_map(
                    lambda t, ii: eng.mix(t, agent_index=ii[0]),
                    mesh=mesh, in_specs=(P("data"), P("data")),
                    out_specs=P("data"), axis_names={"data"},
                    check_vma=False)
                with jax.set_mesh(mesh):
                    outs.append(jax.jit(fn)(X, ids))
            np.testing.assert_allclose(np.asarray(outs[0]),
                                       np.asarray(outs[1]), atol=1e-5)
        print("PSUM_IMPL_OK")
    """)
    assert "PSUM_IMPL_OK" in out


# -- compressed wire: cross-backend tolerance contract ----------------------
#
# dense/pallas compress one concatenated per-agent buffer, ppermute
# compresses per-leaf payloads — different scale granularity, so the
# backends agree within a quantization tolerance rather than bitwise.
# The `none` compressor must be exact everywhere (and its EF residual a
# true zero).


def test_none_compressor_ef_residual_exactly_zero():
    """Regression: the identity compressor's EF recursion must produce
    bit-exact zero residuals and the exact uncompressed combine."""
    from repro.consensus import CompressionConfig
    spec = _specs()["erdos-renyi"]
    tree = _tree(jax.random.PRNGKey(7))
    zeros = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, jnp.float32), tree)
    ef = {"e": zeros, "ref": zeros}
    for engine in (DenseEngine(spec, compression=CompressionConfig("none")),
                   PallasEngine(spec,
                                compression=CompressionConfig("none"))):
        # "none" is not wire-active, but the EF plumbing must still be
        # callable (mix_ef is the generic entry point for the step-core)
        mixed, ef_new = engine.mix_ef(tree, ef, t=jnp.zeros((), jnp.int32))
        want = engine.mix(tree)
        for a, b in zip(jax.tree_util.tree_leaves(mixed),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # an inactive wire passes the state through untouched: residual
        # and public copy both stay exactly zero
        for r in jax.tree_util.tree_leaves(ef_new):
            assert np.all(np.asarray(r) == 0.0)


def test_int8_ef_mix_dense_within_quantization_tolerance():
    """int8+EF dense combine: within one quantization step of the clean
    reference, residual bounded by the per-row quantization scale."""
    from repro.consensus import CompressionConfig
    spec = _specs()["ring"]
    tree = _tree(jax.random.PRNGKey(3))
    zeros = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, jnp.float32), tree)
    eng = DenseEngine(spec, compression=CompressionConfig("int8"))
    mixed, ef_new = eng.mix_ef(tree, {"e": zeros, "ref": zeros},
                               t=jnp.zeros((), jnp.int32))
    want = DenseEngine(spec).mix(tree)
    # round one the innovation IS the value (ref = 0): max|row| / 127
    # bounds the elementwise quantization error; mixing is an average so
    # the combine inherits the bound
    bound = max(float(jnp.max(jnp.abs(l))) for l in
                jax.tree_util.tree_leaves(tree)) / 127.0 + 1e-6
    for a, b in zip(jax.tree_util.tree_leaves(mixed),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= bound
    for r in jax.tree_util.tree_leaves(ef_new["e"]):
        assert float(jnp.max(jnp.abs(r))) <= bound


def test_int8_compression_dense_and_ppermute_tolerance_contract():
    """CompressionConfig("int8") on dense AND ppermute: both stay within
    one quantization step of the uncompressed dense reference, and the
    two compressed backends agree to the same tolerance."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.consensus import (CompressionConfig, DenseEngine,
                                     PermuteEngine)
        from repro.core import erdos_renyi_adjacency, laplacian_mixing

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = laplacian_mixing(erdos_renyi_adjacency(m, 0.5, seed=11))
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (m, 37, 5)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (m, 131))}
        zeros = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, jnp.float32), tree)
        ef = {"e": zeros, "ref": zeros}
        comp = CompressionConfig("int8")
        t0 = jnp.zeros((), jnp.int32)

        ref = DenseEngine(spec).mix(tree)
        md, _ = DenseEngine(spec, compression=comp).mix_ef(tree, ef, t0)

        eng = PermuteEngine(spec, agent_axes=("data",), compression=comp)
        fn = jax.shard_map(lambda t, r: eng.mix_ef(t, r, t0), mesh=mesh,
                           in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")),
                           axis_names={"data"}, check_vma=False)
        with jax.set_mesh(mesh):
            mp, efp = jax.jit(fn)(tree, ef)

        bound = max(float(jnp.max(jnp.abs(l)))
                    for l in jax.tree_util.tree_leaves(tree)) / 127.0 + 1e-6
        for a, b, r in zip(jax.tree_util.tree_leaves(md),
                           jax.tree_util.tree_leaves(mp),
                           jax.tree_util.tree_leaves(ref)):
            assert float(jnp.max(jnp.abs(a - r))) <= bound     # dense vs ref
            assert float(jnp.max(jnp.abs(b - r))) <= bound     # ppermute vs ref
            assert float(jnp.max(jnp.abs(a - b))) <= 2 * bound # cross-backend
        for r in jax.tree_util.tree_leaves(efp["e"]):
            assert float(jnp.max(jnp.abs(r))) <= bound         # EF bounded
        print("INT8_CONTRACT_OK")
    """)
    assert "INT8_CONTRACT_OK" in out


def test_dp_noise_dense_reference_tolerance_contract():
    """Legacy DP wire (ppermute) vs the clean dense reference: the
    perturbation is bounded by the noise scale times the off-diagonal
    mass (the self term mixes clean), on both ppermute and psum impls."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.consensus import DenseEngine, PermuteEngine
        from repro.core import erdos_renyi_adjacency, laplacian_mixing

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = laplacian_mixing(erdos_renyi_adjacency(m, 0.5, seed=11))
        X = jax.random.normal(jax.random.PRNGKey(0), (m, 64))
        ids = jnp.arange(m, dtype=jnp.int32)
        ref = DenseEngine(spec).mix(X)
        sigma = 0.05
        for impl in ("ppermute", "psum"):
            eng = PermuteEngine(spec, agent_axes=("data",),
                                dp_sigma=sigma, impl=impl)
            fn = jax.shard_map(
                lambda t, ii: eng.mix(t, dp_key=jax.random.PRNGKey(5),
                                      agent_index=ii[0]),
                mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=P("data"), axis_names={"data"}, check_vma=False)
            with jax.set_mesh(mesh):
                got = jax.jit(fn)(X, ids)
            diff = np.abs(np.asarray(got) - np.asarray(ref))
            assert diff.max() > 1e-5            # noise actually applied
            # 6-sigma on a weighted sum of <= m unit-variance Gaussians
            assert diff.max() < 6 * sigma * np.sqrt(m), diff.max()
        print("DP_CONTRACT_OK")
    """)
    assert "DP_CONTRACT_OK" in out


def test_sign1bit_ef_solver_paths_agree_dense_vs_pallas():
    """A compressed full-solver trajectory (sign1bit+EF) matches between
    the dense and pallas backends — the wire path composes through the
    same base mixes on both."""
    from repro.solvers import CompressionConfig, SolverConfig, solve
    comp = CompressionConfig("sign1bit", compress_after=1)
    kw = dict(num_steps=3, record_every=0, num_agents=4, n_per_agent=40)
    rd = solve(SolverConfig(algo="interact", alpha=0.05, beta=0.05,
                            backend="dense", compression=comp), **kw)
    rp = solve(SolverConfig(algo="interact", alpha=0.05, beta=0.05,
                            backend="pallas", compression=comp), **kw)
    for a, b in zip(jax.tree_util.tree_leaves(rd.state.x),
                    jax.tree_util.tree_leaves(rp.state.x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
    for a, b in zip(jax.tree_util.tree_leaves(rd.state.ef),
                    jax.tree_util.tree_leaves(rp.state.ef)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
