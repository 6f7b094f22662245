"""The time-varying topology subsystem (docs/TOPOLOGY.md).

Contracts:

* Every matrix realized by every registered process is symmetric,
  doubly stochastic and nonnegative (``validate_mixing``) after the
  link-drop / straggler self-loop repair, and the edge mask is a
  symmetric off-diagonal subset of the base adjacency.
* Schedules are bit-reproducible from the seed, a longer period is a
  strict prefix extension, and ``p = 0`` reproduces the base matrix
  bitwise — so the static process is a no-op through the whole solver.
* The per-call ``matrix=`` operand agrees across dense / pallas /
  ppermute backends, and the sweep engine batches a failure-rate x
  seed grid into one dispatch (with an actionable error anywhere a
  stream cannot be a traced operand).
* Wire accounting prices per link: a dropped link ships zero bytes.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.consensus import DenseEngine, PallasEngine
from repro.consensus.compress import CompressionConfig
from repro.core import (
    HypergradConfig,
    MLPMetaProblem,
    erdos_renyi_adjacency,
    init_head,
    init_mlp_backbone,
    laplacian_mixing,
    make_synthetic_agents,
    validate_mixing,
)
from repro.sharding.collectives import permute_schedule
from repro.solvers import SolverConfig, expand_grid, make_solver, sweep
from repro.topology import (
    AdaptiveTopology,
    PermuteStreamTopology,
    StreamTopology,
    TopologyProcessConfig,
    adaptive_mixing,
    adjacency_of,
    attach_topology,
    available_topology_processes,
    make_topology_process,
    masked_mixing,
    realize_stream,
    stream_of,
    stream_wire_bytes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

M = 6
SPEC = laplacian_mixing(erdos_renyi_adjacency(M, 0.5, seed=11))
STREAM_KINDS = ("static", "link-failure", "straggler", "random-gossip")


def _stream(kind, p=0.35, seed=3, steps=12, spec=SPEC, **kw):
    cfg = TopologyProcessConfig(kind=kind, p=p, **kw)
    return realize_stream(cfg, spec, seed, num_steps=steps)


# -- process properties ----------------------------------------------------

@pytest.mark.parametrize("kind", STREAM_KINDS)
def test_every_realized_matrix_is_a_valid_mixing(kind):
    """Section-4.1 properties hold for every step of every process."""
    adj = adjacency_of(SPEC)
    s = _stream(kind)
    assert s.matrices.shape == (12, M, M)
    for t in range(s.num_steps):
        mat, mask = s.matrices[t], s.edge_mask[t]
        validate_mixing(mat, adj)
        assert (mat >= 0).all()
        assert not mask.diagonal().any()
        assert (mask == mask.T).all()
        assert not (mask & (adj <= 0)).any()   # subset of the base graph


def test_masked_mixing_repair_any_symmetric_mask():
    """The repair rule is valid for arbitrary symmetric drops, and a
    no-drop mask reproduces the base bitwise (exact +0.0 diagonal)."""
    base = SPEC.matrix
    rng = np.random.default_rng(0)
    for _ in range(10):
        up = np.triu(rng.random((M, M)) < 0.5, k=1)
        keep = up | up.T
        validate_mixing(masked_mixing(base, keep), adjacency_of(SPEC))
        assert (masked_mixing(base, keep) >= 0).all()
    full = ~np.eye(M, dtype=bool)
    assert (masked_mixing(base, full) == base).all()


@pytest.mark.parametrize("kind", ["link-failure", "straggler"])
def test_p_zero_reproduces_base_bitwise(kind):
    s = _stream(kind, p=0.0)
    assert (s.matrices == SPEC.matrix[None]).all()
    assert (s.edge_mask == (adjacency_of(SPEC) > 0)[None]).all()


def test_streams_bit_reproducible_and_prefix_stable():
    """Step t depends only on (seed, t): same seed -> identical stream,
    longer stream -> strict prefix, different seed -> different draws."""
    a = _stream("link-failure", seed=5, steps=8)
    b = _stream("link-failure", seed=5, steps=8)
    assert (a.matrices == b.matrices).all()
    assert (a.edge_mask == b.edge_mask).all()
    longer = _stream("link-failure", seed=5, steps=16)
    assert (longer.matrices[:8] == a.matrices).all()
    other = _stream("link-failure", seed=6, steps=8)
    assert not (other.edge_mask == a.edge_mask).all()


def test_gossip_rounds_are_matchings():
    """At most one partner per agent per round; matched pairs average."""
    s = _stream("random-gossip", seed=1, steps=20)
    for t in range(s.num_steps):
        deg = s.edge_mask[t].sum(axis=1)
        assert deg.max() <= 1
        mat = s.matrices[t]
        for i, j in np.argwhere(s.edge_mask[t]):
            assert mat[i, j] == 0.5 and mat[i, i] == 0.5


def test_stream_padding_ghosts_are_identity_rows():
    s = _stream("link-failure", seed=2, steps=4)
    p = s.padded(9)
    assert (p.matrices[:, :M, :M] == s.matrices).all()
    assert (p.matrices[:, M:, :] == np.eye(9)[None, M:, :]).all()
    assert not p.edge_mask[:, M:, :].any()
    with pytest.raises(ValueError, match="cannot pad"):
        s.padded(3)
    assert 0.0 <= p.mean_spectral_gap <= 1.0


def test_registry_and_config_validation():
    assert set(STREAM_KINDS) <= set(available_topology_processes())
    with pytest.raises(ValueError, match="unknown topology process"):
        make_topology_process(TopologyProcessConfig(kind="smoke-signals"))
    with pytest.raises(ValueError, match="p must be in"):
        TopologyProcessConfig(kind="link-failure", p=1.5)
    with pytest.raises(ValueError, match="period must be"):
        TopologyProcessConfig(period=0)
    with pytest.raises(ValueError, match="tau must be"):
        TopologyProcessConfig(tau=0.0)
    with pytest.raises(ValueError, match="state-dependent"):
        realize_stream(TopologyProcessConfig(kind="adaptive"), SPEC, 0)


def test_wire_bytes_priced_per_link():
    """p = 0 prices every base link each round; all-dropped rounds are
    free; the totals compose with the communication interval."""
    size = 100
    links = int(adjacency_of(SPEC).sum())        # directed link count
    s0 = _stream("link-failure", p=0.0, steps=4)
    got = stream_wire_bytes(s0, None, size, 4)
    assert got == [2 * 4 * size * links * t for t in range(5)]
    dead = _stream("straggler", p=1.0, steps=4)
    assert stream_wire_bytes(dead, None, size, 4) == [0] * 5
    every2 = stream_wire_bytes(s0, CompressionConfig(), size, 4,
                               communication_interval=2)
    assert every2[-1] == got[-1] // 2


# -- in-scan runtimes ------------------------------------------------------

def test_adaptive_mixing_properties():
    """Symmetric, rows sum to 1, nonnegative, base-graph sparsity — and
    a zero adjacency row (a ghost-padded agent) yields an identity row."""
    adj = adjacency_of(SPEC)
    x2d = jax.random.normal(jax.random.PRNGKey(0), (M, 7))
    w = np.asarray(adaptive_mixing(x2d, jnp.asarray(adj, jnp.float32),
                                   tau=1.0), np.float64)
    validate_mixing(w, adj, atol=1e-5)
    assert (w >= -1e-7).all()
    ghost_adj = adj.copy()
    ghost_adj[-1, :] = ghost_adj[:, -1] = 0.0
    wg = np.asarray(adaptive_mixing(x2d, jnp.asarray(ghost_adj,
                                                     jnp.float32), 1.0))
    np.testing.assert_allclose(wg[-1], np.eye(M)[-1], atol=1e-6)


def test_adaptive_topology_needs_the_iterates():
    topo = AdaptiveTopology(adjacency_of(SPEC), tau=1.0)
    with pytest.raises(ValueError, match="adaptive topology"):
        topo.matrix_at(0, None)


def test_attach_topology_static_is_a_noop():
    eng = DenseEngine(SPEC)
    attach_topology(eng, TopologyProcessConfig(), SPEC, seed=0)
    assert eng.topology is None and stream_of(eng) is None
    assert eng.topology_matrix(None) is None    # no t needed when static


def test_stream_topology_wraps_by_period():
    s = _stream("link-failure", seed=4, steps=3)
    topo = StreamTopology(s.matrices)
    np.testing.assert_array_equal(np.asarray(topo.matrix_at(5)),
                                  np.asarray(topo.matrix_at(2)))
    eng = DenseEngine(SPEC)
    attach_topology(eng, TopologyProcessConfig(kind="link-failure", p=0.3,
                                               period=3), SPEC, seed=4)
    with pytest.raises(ValueError, match="step index"):
        eng.mix_ef({"w": jnp.zeros((M, 2))}, None, None)


def test_permute_stream_weights_match_matrices():
    sched = permute_schedule(SPEC)
    s = _stream("link-failure", seed=7, steps=5)
    topo = PermuteStreamTopology(sched, s.matrices)
    idx = np.arange(M)
    for t in (0, 3):
        pw = topo.matrix_at(t)
        np.testing.assert_allclose(np.asarray(pw.self_weights),
                                   s.matrices[t].diagonal(), atol=1e-6)
        for k, o in enumerate(sched.offsets):
            np.testing.assert_allclose(
                np.asarray(pw.weights)[k],
                s.matrices[t][idx, (idx + o) % M], atol=1e-6)


def test_permute_stream_rejects_stray_edges():
    """A stream placing weight off the base offsets cannot share the
    base ppermute schedule — it must fail loudly, not mix wrongly."""
    from repro.core import ring_mixing
    ring = ring_mixing(M)
    with pytest.raises(ValueError, match="outside the base schedule"):
        PermuteStreamTopology(permute_schedule(ring),
                              _stream("link-failure", p=0.0).matrices)


def test_adaptive_on_ppermute_raises():
    from repro.consensus import PermuteEngine
    eng = PermuteEngine(SPEC, agent_axes=("data",))
    with pytest.raises(ValueError, match="dense or pallas"):
        attach_topology(eng, TopologyProcessConfig(kind="adaptive"),
                        SPEC, seed=0)


# -- cross-backend parity --------------------------------------------------

def _tree(key, m=M):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (m, 11, 3)),
            "b": jax.random.normal(k2, (m, 17))}


def test_dense_and_pallas_agree_under_stream():
    """The fused pallas step resolves the same per-step matrix as the
    dense reference when both carry the same realized stream."""
    proc = TopologyProcessConfig(kind="link-failure", p=0.4, period=8)
    dense = attach_topology(DenseEngine(SPEC), proc, SPEC, seed=9)
    pallas = attach_topology(PallasEngine(SPEC, interpret=True), proc,
                             SPEC, seed=9)
    x = _tree(jax.random.PRNGKey(0))
    u = jax.tree_util.tree_map(lambda l: 0.5 * l, x)
    p = jax.tree_util.tree_map(lambda l: 0.1 * l, x)
    for t in (0, 3, 7):
        xd, ud = dense.step1_step3(x, u, p, p, 0.3, t=t)
        xp, up = pallas.step1_step3(x, u, p, p, 0.3, t=t)
        for a, b in zip(jax.tree_util.tree_leaves((xd, ud)),
                        jax.tree_util.tree_leaves((xp, up))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
    # the stream genuinely varies: step 0 and step 3 matrices differ
    st = stream_of(dense)
    assert not (st.matrices[0] == st.matrices[3]).all()


def run_in_subprocess(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_ppermute_matches_dense_under_stream():
    """The shared-offset-schedule form (per-step PermuteWeights) mixes
    identically to the dense gather of the same stream, on 8 forced
    host devices."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.consensus import DenseEngine, PermuteEngine
        from repro.core import erdos_renyi_adjacency, laplacian_mixing
        from repro.topology import TopologyProcessConfig, attach_topology

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = laplacian_mixing(erdos_renyi_adjacency(m, 0.5, seed=11))
        proc = TopologyProcessConfig(kind="link-failure", p=0.4, period=12)
        dense = attach_topology(DenseEngine(spec), proc, spec, seed=9)
        eng = attach_topology(PermuteEngine(spec, agent_axes=("data",)),
                              proc, spec, seed=9)
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (m, 13, 3)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (m, 29))}
        for t in (0, 3, 7, 11):
            fn = jax.shard_map(
                lambda tr: eng.mix(tr, matrix=eng.topology.matrix_at(t)),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                axis_names={"data"}, check_vma=False)
            with jax.set_mesh(mesh):
                got = jax.jit(fn)(tree)
            want = dense.mix(tree, matrix=dense.topology.matrix_at(t))
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
        print("STREAM_BACKENDS_OK")
    """)
    assert "STREAM_BACKENDS_OK" in out


# -- solver + sweep integration -------------------------------------------

@pytest.fixture(scope="module")
def setup():
    m = 4
    data = make_synthetic_agents(jax.random.PRNGKey(0), num_agents=m,
                                 n_per_agent=60, d_in=8, num_classes=3)
    prob = MLPMetaProblem(mu_g=0.5, lipschitz_g=4.0)
    x0 = init_mlp_backbone(jax.random.PRNGKey(1), 8, hidden=8)
    y0 = init_head(jax.random.PRNGKey(2), 8, 3)
    spec = laplacian_mixing(erdos_renyi_adjacency(m, 0.8, seed=3))
    hg = HypergradConfig(method="cg", cg_iters=8)
    return prob, x0, y0, data, spec, hg


def _config(setup, **kw):
    _, _, _, _, spec, hg = setup
    base = dict(algo="interact", alpha=0.1, beta=0.1, batch_size=6, q=5,
                mixing=spec, hypergrad=hg, seed=7)
    base.update(kw)
    return SolverConfig(**base)


def test_static_process_is_bitwise_noop_through_solver(setup):
    prob, x0, y0, data, _, hg = setup
    traces = []
    for proc in (TopologyProcessConfig(),
                 TopologyProcessConfig(kind="static", p=0.0)):
        solver = make_solver(_config(setup, topology_process=proc))
        state = solver.init(None, prob, hg, x0, y0, data)
        _, tr = solver.run_traced(state, data, 4, 2, None)
        traces.append(np.asarray(tr))
    np.testing.assert_array_equal(traces[0], traces[1])


def test_solver_backends_agree_under_link_failure(setup):
    """End-to-end: dense and pallas solvers walk the same perturbed
    trajectory when the config carries a link-failure process."""
    prob, x0, y0, data, _, hg = setup
    proc = TopologyProcessConfig(kind="link-failure", p=0.3, period=8)
    finals = []
    for backend in ("dense", "pallas"):
        solver = make_solver(_config(setup, topology_process=proc,
                                     backend=backend))
        state = solver.init(None, prob, hg, x0, y0, data)
        state, _ = solver.run_traced(state, data, 3, 0, None)
        finals.append([np.asarray(l) for l in
                       jax.tree_util.tree_leaves(state.x)])
    for a, b in zip(*finals):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_static_key_groups_failure_rates_not_kinds(setup):
    lf = lambda p: TopologyProcessConfig(kind="link-failure", p=p)
    a = _config(setup, topology_process=lf(0.0))
    b = _config(setup, topology_process=lf(0.3), seed=8)
    c = _config(setup, topology_process=TopologyProcessConfig(
        kind="random-gossip"))
    assert a.static_key() == b.static_key()     # p/seed batch together
    assert a.static_key() != c.static_key()     # kind splits the group
    assert a.static_key() != _config(setup).static_key()


def test_sweep_batches_failure_grid_and_p0_matches_static(setup):
    """p x seed in ONE dispatch; the p = 0 row is bitwise the static
    baseline row; ``trace_of`` disambiguates rows that differ only in
    the process realization."""
    prob, x0, y0, data, _, _ = setup
    base = sweep([_config(setup)], 4, 2, problem=prob, x0=x0, y0=y0,
                 data=data)
    lf = lambda p: TopologyProcessConfig(kind="link-failure", p=p,
                                         period=4)
    configs = expand_grid(_config(setup),
                          topology_process=(lf(0.0), lf(0.5)),
                          seed=(7, 8))
    res = sweep(configs, 4, 2, problem=prob, x0=x0, y0=y0, data=data)
    assert res.num_dispatches == 1
    np.testing.assert_array_equal(res.traces[0], base.traces[0])
    assert not np.array_equal(res.traces[0], res.traces[2])  # p bites
    np.testing.assert_array_equal(res.trace_of(configs[2]),
                                  res.traces[2])
    np.testing.assert_array_equal(res.trace_of(configs[0]),
                                  res.traces[0])


def test_sweep_mixed_streams_off_dense_raise_actionably(setup):
    """pallas cannot take the stream as a traced vmap operand — mixing
    realizations there must name the offending configs, not silently
    run them all on one stream."""
    prob, x0, y0, data, _, _ = setup
    lf = lambda p: TopologyProcessConfig(kind="link-failure", p=p)
    configs = [_config(setup, backend="pallas", topology_process=lf(p))
               for p in (0.1, 0.4)]
    with pytest.raises(ValueError, match=r"configs\[1\].*p=0\.4"):
        sweep(configs, 3, 0, problem=prob, x0=x0, y0=y0, data=data)


def test_sweep_single_stream_bakes_on_pallas(setup):
    """One shared (p, seed) realization needs no traced operand: the
    pallas group bakes the stream and still batches the seeds."""
    prob, x0, y0, data, _, _ = setup
    proc = TopologyProcessConfig(kind="link-failure", p=0.3, seed=5,
                                 period=4)
    configs = [_config(setup, backend="pallas", topology_process=proc,
                       seed=s) for s in (7, 8)]
    res = sweep(configs, 3, 0, problem=prob, x0=x0, y0=y0, data=data)
    assert res.num_dispatches == 1
    assert all(np.isfinite(t).all() for t in res.traces)
