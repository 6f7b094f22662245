"""Byzantine-resilience subsystem: attacks, robust combines, guards.

Covers the contract docs/BYZANTINE.md states: attack-schedule
determinism and pad-safety, ghost-pad invariance of every combine rule,
robust-rule properties (permutation invariance, loud breakdown errors),
the weighted rule's bitwise no-op through all four registry solvers,
the EF-compression x attack interaction (CHOCO refs track the
post-attack payload), dense-vs-ppermute parity for ``weighted`` under
attack, the in-scan divergence guard, and the sweep-batching story
(static-key participation, one dispatch per attack grid).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.byzantine import (
    ByzantineConfig, GuardConfig, apply_attack, attack_names,
    byzantine_mask, combine_rule_names, make_attack, robust_combine,
)
from repro.consensus import DenseEngine, init_ef
from repro.consensus.compress import CompressionConfig
from repro.core import (HypergradConfig, erdos_renyi_adjacency,
                        laplacian_mixing)
from repro.core.consensus import pad_mixing
from repro.solvers import SolverConfig, expand_grid, solve, sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RULES = ("weighted", "coordinate-median", "trimmed-mean", "krum-like")
ALGOS = ("interact", "svr-interact", "gt-dsgd", "d-sgd")

M = 5


def _spec(m=M, p=0.8, seed=2):
    return laplacian_mixing(erdos_renyi_adjacency(m, p, seed=seed))


def _tree(key, m=M):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (m, 7, 3)),
            "b": jax.random.normal(k2, (m, 11))}


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _tiny_cfg(**kw):
    return SolverConfig(hypergrad=HypergradConfig(method="cg", cg_iters=4),
                        **kw)


def _cheap_metric(state):
    return sum(jnp.sum(jnp.abs(l)) for l in _leaves(state.x))


# -- attacks -----------------------------------------------------------


def test_attack_registry_names():
    assert set(attack_names()) >= {"sign-flip", "gaussian", "same-value",
                                   "inner-outer-split"}
    with pytest.raises(ValueError, match="unknown attack"):
        make_attack("carrier-pigeon")


def test_attack_determinism_and_step_variation():
    key = jax.random.PRNGKey(3)
    tree = _tree(jax.random.PRNGKey(0))
    mask = byzantine_mask(key, M, 2)
    atk = make_attack("gaussian")
    k0 = jax.random.fold_in(key, 0)
    a = apply_attack(atk, tree, mask, k0, 1.5)
    b = apply_attack(atk, tree, mask, k0, 1.5)
    for x, y in zip(_leaves(a), _leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    # a different per-round key draws different corruption
    c = apply_attack(atk, tree, mask, jax.random.fold_in(key, 1), 1.5)
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(_leaves(a), _leaves(c)))


def test_attack_honest_rows_bitwise():
    key = jax.random.PRNGKey(3)
    tree = _tree(jax.random.PRNGKey(0))
    mask = byzantine_mask(key, M, 1)
    got = apply_attack(make_attack("sign-flip"), tree, mask, key, 2.0)
    m_np = np.asarray(mask)
    assert m_np.sum() == 1
    for orig, out in zip(_leaves(tree), _leaves(got)):
        assert np.array_equal(np.asarray(orig)[~m_np],
                              np.asarray(out)[~m_np])
        assert np.array_equal(np.asarray(out)[m_np],
                              -2.0 * np.asarray(orig)[m_np])
    # zero attackers: every row bitwise
    clean = apply_attack(make_attack("sign-flip"), tree,
                         byzantine_mask(key, M, 0), key, 2.0)
    for orig, out in zip(_leaves(tree), _leaves(clean)):
        assert np.array_equal(np.asarray(orig), np.asarray(out))


def test_same_value_attack_colludes():
    key = jax.random.PRNGKey(4)
    tree = {"w": jax.random.normal(key, (M, 6))}
    mask = jnp.ones((M,), bool)
    out = np.asarray(apply_attack(make_attack("same-value"), tree, mask,
                                  key, 1.0)["w"])
    assert np.array_equal(out, np.broadcast_to(out[0], out.shape))


def test_inner_outer_split_targets_u_stream_only():
    assert make_attack("inner-outer-split").streams == ("u",)
    assert make_attack("sign-flip").streams == ("x", "u")


def test_byzantine_mask_fixed_subset_and_pad_safe():
    key = jax.random.PRNGKey(9)
    small = np.asarray(byzantine_mask(key, 5, 2))
    padded = np.asarray(byzantine_mask(key, 8, 2, num_active=5))
    assert small.sum() == 2 and padded.sum() == 2
    assert np.array_equal(small, padded[:5])   # same active subset
    assert not padded[5:].any()                # ghosts never attack
    # num_byzantine may be traced
    traced = jax.jit(lambda nb: byzantine_mask(key, 5, nb))(jnp.int32(2))
    assert np.array_equal(small, np.asarray(traced))


# -- combine rules -----------------------------------------------------


@pytest.mark.parametrize("rule", RULES)
def test_ghost_pad_invariance(rule):
    """Poisoned ghost rows leave active agents' aggregates bitwise."""
    spec = _spec()
    tree = _tree(jax.random.PRNGKey(1))
    want = robust_combine(jnp.asarray(spec.matrix), tree, rule, 1)
    padded_mat = jnp.asarray(pad_mixing(spec, 8))
    poison = jax.tree_util.tree_map(
        lambda l: jnp.concatenate(
            [l, jnp.full((8 - M,) + l.shape[1:], 1e30, l.dtype)]), tree)
    got = robust_combine(padded_mat, poison, rule, 1)
    for a, b in zip(_leaves(want), _leaves(got)):
        assert np.array_equal(np.asarray(a), np.asarray(b)[:M])


@pytest.mark.parametrize("rule", ["coordinate-median", "trimmed-mean"])
def test_permutation_invariance(rule):
    """combine(P M P^T, P X) == P combine(M, X) for the order-free rules."""
    mat = jnp.asarray(_spec().matrix, jnp.float32)
    vals = {"w": jax.random.normal(jax.random.PRNGKey(5), (M, 9))}
    perm = np.array([3, 0, 4, 1, 2])
    p_mat = mat[perm][:, perm]
    p_vals = {"w": vals["w"][perm]}
    base = np.asarray(robust_combine(mat, vals, rule, 1)["w"])
    permuted = np.asarray(robust_combine(p_mat, p_vals, rule, 1)["w"])
    np.testing.assert_allclose(permuted, base[perm], atol=1e-6)


def test_trimmed_mean_screens_one_outlier():
    """On a complete graph, trim=1 removes a single huge row exactly."""
    mat = jnp.full((M, M), 1.0 / M, jnp.float32)
    honest = jnp.broadcast_to(jnp.arange(4.0), (M, 4)).copy()
    vals = {"w": honest.at[2].set(1e6)}
    out = np.asarray(robust_combine(mat, vals, "trimmed-mean", 1)["w"])
    np.testing.assert_allclose(out, np.broadcast_to(np.arange(4.0),
                                                    (M, 4)), atol=1e-5)


def test_breakdown_and_config_validation_raise():
    with pytest.raises(ValueError, match="unknown attack"):
        ByzantineConfig(kind="nope")
    with pytest.raises(ValueError, match="unknown combine rule"):
        ByzantineConfig(combine="nope")
    with pytest.raises(ValueError, match="trimmed-mean breakdown"):
        DenseEngine(_spec(), byzantine=ByzantineConfig(
            combine="trimmed-mean", trim=3))
    with pytest.raises(ValueError, match="honest agent"):
        DenseEngine(_spec(), byzantine=ByzantineConfig(
            kind="sign-flip", num_byzantine=5))


def test_ppermute_refuses_robust_rules():
    from repro.consensus import PermuteEngine
    from repro.core import ring_mixing
    with pytest.raises(NotImplementedError, match="dense backend"):
        PermuteEngine(ring_mixing(8, self_weight=1 / 3),
                      byzantine=ByzantineConfig(combine="trimmed-mean",
                                                trim=1))


def test_combine_rule_registry_names():
    assert set(combine_rule_names()) == set(RULES)


# -- the engine wire path ----------------------------------------------


def test_engine_weighted_rule_is_plain_mix():
    spec = _spec()
    tree = _tree(jax.random.PRNGKey(2))
    plain = DenseEngine(spec)
    byz = DenseEngine(spec, byzantine=ByzantineConfig(
        kind="sign-flip", num_byzantine=0))
    a, _ = byz.mix_ef(tree, None, 0)
    b = plain.mix(tree)
    for x, y in zip(_leaves(a), _leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_ef_refs_track_post_attack_payload():
    """CHOCO refs must advance by what was actually transmitted: the
    byzantine engine on a clean tree equals a plain engine fed the
    pre-attacked tree — payload and wire state bitwise."""

    class CaptureDense(DenseEngine):
        def _combine(self, tree, **kw):
            self.captured = tree
            return super()._combine(tree, **kw)

    spec = _spec()
    comp = CompressionConfig(kind="sign1bit")
    bcfg = ByzantineConfig(kind="sign-flip", num_byzantine=1, scale=2.0,
                           seed=5)
    byz = CaptureDense(spec, compression=comp, byzantine=bcfg)
    plain = CaptureDense(spec, compression=comp)
    tree = _tree(jax.random.PRNGKey(6))
    attacked = byz._attack_payload(tree, 0, "x")
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(_leaves(tree), _leaves(attacked)))

    _, ef_b = byz.mix_ef(tree, init_ef(comp, x=tree)["x"], 0, stream="x")
    _, ef_p = plain.mix_ef(attacked, init_ef(comp, x=attacked)["x"], 0)
    for a, b in zip(_leaves(byz.captured), _leaves(plain.captured)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(_leaves(ef_b["ref"]), _leaves(ef_p["ref"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the ref IS the decoded payload the neighbours combined
    for a, b in zip(_leaves(ef_b["ref"]), _leaves(byz.captured)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_dense_vs_ppermute_weighted_under_attack():
    """The sharded backend corrupts the same slots with the same draws
    as the dense reference (global slot ids thread through shard_map)."""
    out = _run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.byzantine import ByzantineConfig
        from repro.consensus import DenseEngine, PermuteEngine
        from repro.core import ring_mixing

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 8
        spec = ring_mixing(m, self_weight=1/3)
        bcfg = ByzantineConfig(kind="gaussian", num_byzantine=2,
                               scale=3.0, seed=7)
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (m, 37, 5)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (m, 131))}
        dense = DenseEngine(spec, byzantine=bcfg)
        eng = PermuteEngine(spec, agent_axes=("data",), byzantine=bcfg)
        fn = jax.shard_map(lambda t: eng.mix_ef(t, None, 0, stream="x")[0],
                           mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                           axis_names={"data"}, check_vma=False)
        with jax.set_mesh(mesh):
            got = jax.jit(fn)(tree)
        want, _ = dense.mix_ef(tree, None, 0, stream="x")
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        print("PARITY_OK")
    """)
    assert "PARITY_OK" in out


def _run_in_subprocess(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


# -- solvers end to end ------------------------------------------------


@pytest.mark.parametrize("algo", ALGOS)
def test_weighted_zero_attackers_bitwise_all_solvers(algo):
    clean = solve(_tiny_cfg(algo=algo), 3, 1, num_agents=4,
                  n_per_agent=32, metric_fn=_cheap_metric,
                  measure_hypergrad=False)
    byz = solve(_tiny_cfg(algo=algo, byzantine=ByzantineConfig(
        kind="sign-flip", num_byzantine=0)), 3, 1, num_agents=4,
        n_per_agent=32, metric_fn=_cheap_metric, measure_hypergrad=False)
    assert np.array_equal(np.asarray(clean.trace), np.asarray(byz.trace))
    for a, b in zip(_leaves(clean.state.x), _leaves(byz.state.x)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_attack_changes_trajectory_and_inner_split_spares_dsgd():
    base = solve(_tiny_cfg(algo="gt-dsgd"), 3, 1, num_agents=4,
                 n_per_agent=32, metric_fn=_cheap_metric,
                 measure_hypergrad=False)
    hit = solve(_tiny_cfg(algo="gt-dsgd", byzantine=ByzantineConfig(
        kind="inner-outer-split", num_byzantine=1, scale=5.0)), 3, 1,
        num_agents=4, n_per_agent=32, metric_fn=_cheap_metric,
        measure_hypergrad=False)
    assert not np.array_equal(np.asarray(base.trace),
                              np.asarray(hit.trace))
    # d-sgd ships only x: the u-stream attack cannot touch it
    d_base = solve(_tiny_cfg(algo="d-sgd"), 3, 1, num_agents=4,
                   n_per_agent=32, metric_fn=_cheap_metric,
                   measure_hypergrad=False)
    d_hit = solve(_tiny_cfg(algo="d-sgd", byzantine=ByzantineConfig(
        kind="inner-outer-split", num_byzantine=1, scale=5.0)), 3, 1,
        num_agents=4, n_per_agent=32, metric_fn=_cheap_metric,
        measure_hypergrad=False)
    assert np.array_equal(np.asarray(d_base.trace),
                          np.asarray(d_hit.trace))


def test_guard_trips_and_surfaces_counters():
    res = solve(_tiny_cfg(algo="gt-dsgd",
                          byzantine=ByzantineConfig(kind="sign-flip",
                                                    num_byzantine=1,
                                                    scale=50.0),
                          guard=GuardConfig(nan=True, max_norm=10.0)),
                6, 2, num_agents=4, n_per_agent=32,
                metric_fn=_cheap_metric, measure_hypergrad=False)
    assert res.tripped_steps > 0
    assert 0 <= res.last_good_step <= 6
    for leaf in _leaves(res.state.x):
        assert np.all(np.isfinite(np.asarray(leaf)))
    # unguarded runs keep the default sentinels
    clean = solve(_tiny_cfg(algo="gt-dsgd"), 2, 1, num_agents=4,
                  n_per_agent=32, metric_fn=_cheap_metric,
                  measure_hypergrad=False)
    assert clean.tripped_steps == 0 and clean.last_good_step == -1


def test_guard_clean_run_never_trips():
    res = solve(_tiny_cfg(algo="interact",
                          guard=GuardConfig(nan=True, max_norm=1e6)),
                3, 1, num_agents=4, n_per_agent=32,
                metric_fn=_cheap_metric, measure_hypergrad=False)
    assert res.tripped_steps == 0
    assert res.last_good_step == 3


# -- sweep batching ----------------------------------------------------


def test_static_key_participation():
    base = _tiny_cfg(algo="interact")
    atk = dataclasses.replace(base, byzantine=ByzantineConfig(
        kind="sign-flip", num_byzantine=1))
    # padded: attack values are operands, structure splits groups
    assert atk.static_key(pad_to=8) == dataclasses.replace(
        base, byzantine=ByzantineConfig(kind="sign-flip",
                                        num_byzantine=2, scale=9.0)
    ).static_key(pad_to=8)
    assert atk.static_key(pad_to=8) != base.static_key(pad_to=8)
    assert atk.static_key(pad_to=8) != dataclasses.replace(
        base, byzantine=ByzantineConfig(kind="gaussian",
                                        num_byzantine=1)
    ).static_key(pad_to=8)
    assert atk.static_key(pad_to=8) != dataclasses.replace(
        base, byzantine=ByzantineConfig(combine="coordinate-median")
    ).static_key(pad_to=8)
    # non-padded: a seed-inheriting attack splits on the config seed
    # (the built engine bakes the schedule key as a constant)
    s0 = dataclasses.replace(atk, seed=0)
    s1 = dataclasses.replace(atk, seed=1)
    assert s0.static_key() != s1.static_key()
    pinned = ByzantineConfig(kind="sign-flip", num_byzantine=1, seed=5)
    assert (dataclasses.replace(s0, byzantine=pinned).static_key()
            == dataclasses.replace(s1, byzantine=pinned).static_key())
    # guards are trace-structural too
    assert base.static_key() != dataclasses.replace(
        base, guard=GuardConfig(nan=True)).static_key()


def test_padded_attack_grid_single_dispatch_and_bitwise_zero():
    def masked_metric(state, data, num_active):
        rows = _leaves(state.x)[0].shape[0]
        keep = jnp.arange(rows) < num_active
        return sum(jnp.sum(jnp.where(
            keep.reshape((-1,) + (1,) * (l.ndim - 1)), jnp.abs(l), 0.0))
            for l in _leaves(state.x))

    base = _tiny_cfg(algo="interact", num_agents=4)
    grid = expand_grid(
        base,
        byzantine=tuple(ByzantineConfig(kind="sign-flip", num_byzantine=nb,
                                        scale=5.0) for nb in (0, 1)),
        seed=(0, 1))
    res = sweep(grid, 2, 1, num_agents=4, n_per_agent=32,
                pad_agents=True, metric_fn=masked_metric)
    assert res.num_dispatches == 1

    clean = sweep(expand_grid(base, seed=(0, 1)), 2, 1, num_agents=4,
                  n_per_agent=32, pad_agents=True,
                  metric_fn=masked_metric)
    zero_rows = np.stack([res.trace_of(c) for c in grid
                          if c.byzantine.num_byzantine == 0])
    assert np.array_equal(zero_rows, clean.traces)
    attacked = np.stack([res.trace_of(c) for c in grid
                         if c.byzantine.num_byzantine == 1])
    assert not np.array_equal(attacked, clean.traces)
