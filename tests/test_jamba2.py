"""AI21-Jamba2-3B's share against the plain reference, at tiny widths with
the real 14-layer period: attention at layer 7, MQA (one kv head), no
positional encoding, dt_rank and the dt/B/C RMSNorms.

* the model's features and their gradients equal the reference's
  (``bench/reference/jamba2_lm.py``), the selective scan chunked or
  whole; the reference's ``init_point`` has the program's layout;
* a tensor-parallel share: the four ranks' outputs add up to the uncut
  layer for the attention and FFN blocks, and for the Mamba mixer given
  the summed x_proj; the share config initialises exactly the held
  slices;
* ``LMBilevelProblem`` through ``make_solver``: two INTERACT steps, dense
  on one device and ppermute on four forced host devices through
  ``MeshRunner``, against the reference's two steps.
"""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench"))

from reference import jamba2_lm as R  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import mamba as Mb  # noqa: E402
from repro.models import model as M  # noqa: E402

TP = 4
HYPER = dict(mu_g=0.1, lipschitz_g=2.0, neumann_k=4)
ALPHA, BETA = 0.05, 0.1


def tiny(**kw):
    """jamba2-3b's period at tiny widths; every published ratio kept."""
    base = dict(d_model=32, d_ff=64, vocab_size=64, num_heads=4,
                num_kv_heads=1, head_dim=8, mamba_d_state=4,
                mamba_dt_rank=4, mamba_seq_chunk=4, num_layers=14,
                dtype="float32")
    base.update(kw)
    cfg = dataclasses.replace(get_config("jamba2-3b"), **base)
    cfg.validate()
    return cfg


def ref_conf(cfg):
    return dict(rms_norm_eps=cfg.norm_eps)


def point_conf(cfg):
    """The reference's configuration keys for ``cfg``'s held share."""
    return dict(ref_conf(cfg), hidden_size=cfg.d_model,
                head_dim=cfg.resolved_head_dim,
                mamba_d_state=cfg.mamba_d_state,
                mamba_d_conv=cfg.mamba_d_conv,
                mamba_dt_rank=cfg.resolved_dt_rank,
                num_hidden_layers=cfg.num_layers,
                attn_layer_period=cfg.attn_every,
                attn_layer_offset=cfg.attn_offset,
                held=dict(query_heads=cfg.held_heads,
                          kv_heads=cfg.held_kv_heads,
                          mamba_channels=cfg.held_d_inner,
                          ffn_columns=cfg.held_d_ff,
                          vocab_rows=cfg.held_vocab))


def test_period_is_jambas():
    cfg = get_config("jamba2-3b")
    mixers = [s.mixer for s in cfg.layer_pattern()]
    assert len(mixers) == 14 and mixers.index("attn") == 7
    assert mixers.count("attn") == 1 and cfg.num_periods() == 2
    assert (cfg.resolved_head_dim, cfg.num_kv_heads, cfg.use_rope,
            cfg.resolved_dt_rank) == (128, 1, False, 160)
    assert [n for _, n in cfg.layer_runs()] == [7, 1, 6]


def test_reference_point_has_the_program_layout():
    cfg = tiny(num_layers=14, tp_size=TP)
    shapes = lambda t: jax.tree_util.tree_map(jnp.shape, t)
    x, y = R.init_point(point_conf(cfg), jax.random.PRNGKey(0))
    assert shapes(x) == shapes(M.init_params(cfg, jax.random.PRNGKey(0)))
    assert y.shape == M.init_head(cfg, jax.random.PRNGKey(0)).shape


@pytest.mark.parametrize("chunk", [4, 0], ids=["chunked", "whole"])
@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_model_matches_reference(what, chunk):
    cfg = tiny(mamba_seq_chunk=chunk)
    x = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    prog = lambda xx: M.features(cfg, xx, toks)[0]
    ref = lambda xx: R.features(ref_conf(cfg), xx, toks)
    if what == "forward":
        np.testing.assert_allclose(np.asarray(prog(x)), np.asarray(ref(x)),
                                   rtol=2e-4, atol=2e-5)
    else:
        # per-leaf norms: the gradients through dt sum many terms of both
        # signs, so single entries carry float32 cancellation (the float32
        # reference itself reads ~1e-3 of the median leaf against float64)
        a, b = (jax.grad(lambda xx, f=f: jnp.sum(f(xx) * w))(x)
                for f in (prog, ref))
        assert leaf_gap(a, b) < 1e-3


def leaf_gap(a, b) -> float:
    """Worst leaf of ||a - b|| / max(||b leaf||, median leaf norm)."""
    la = [np.asarray(l, np.float64) for l in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(l, np.float64) for l in jax.tree_util.tree_leaves(b)]
    floor = np.median([np.linalg.norm(l) for l in lb])
    return max(float(np.linalg.norm(p - q) / max(np.linalg.norm(q), floor))
               for p, q in zip(la, lb))


def _rank_slice(block: str, p: dict, rank: int, full_cfg) -> dict:
    """Rank ``rank``'s share of an uncut layer's ``block`` parameters."""
    cut = lambda n: slice(rank * n // TP, (rank + 1) * n // TP)
    if block == "attn":
        h = cut(full_cfg.num_heads)
        return dict(p, wq=p["wq"][:, h], wo=p["wo"][h])
    if block == "mlp":
        c = cut(full_cfg.d_ff)
        return dict(p, w_gate=p["w_gate"][:, c], w_up=p["w_up"][:, c],
                    w_down=p["w_down"][c])
    d_in = full_cfg.held_d_inner
    c = cut(d_in)
    return dict(p, w_in=jnp.concatenate(
                    [p["w_in"][:, c], p["w_in"][:, d_in:][:, c]], axis=1),
                conv_w=p["conv_w"][:, c], conv_b=p["conv_b"][c],
                w_x=p["w_x"][c], w_dt=p["w_dt"][:, c],
                dt_bias=p["dt_bias"][c], a_log=p["a_log"][c],
                d_skip=p["d_skip"][c], w_out=p["w_out"][c])


@pytest.mark.parametrize("block", ["attn", "mlp", "mamba"])
def test_share_sums_to_uncut(block):
    full = tiny()
    share = dataclasses.replace(full, tp_size=TP)
    which = 1 if block == "attn" else 0  # runs: 7 Mamba, attention, 6 Mamba
    p_full = jax.tree_util.tree_map(
        lambda l: l[0, 0], M.init_params(full, jax.random.PRNGKey(3))
        ["layers"][which])[block]
    p_share = jax.tree_util.tree_map(
        lambda l: l[0, 0], M.init_params(share, jax.random.PRNGKey(3))
        ["layers"][which])[block]
    shares = [_rank_slice(block, p_full, r, full) for r in range(TP)]
    assert jax.tree_util.tree_map(jnp.shape, p_share) == \
        jax.tree_util.tree_map(jnp.shape, shares[0])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 16, full.d_model))
    if block == "attn":
        run = lambda p: L.attention(
            p, h, jnp.arange(16), num_heads=p["wq"].shape[1],
            num_kv_heads=1, head_dim=full.head_dim, rope_theta=None,
            window=None, logit_softcap=None, qk_norm=False,
            norm_eps=full.norm_eps)[0]
        whole, parts = run(p_full), [run(p) for p in shares]
    elif block == "mlp":
        whole, parts = L.gated_mlp(p_full, h), [L.gated_mlp(p, h)
                                                for p in shares]
    else:
        whole = Mb.mamba_block(p_full, h, seq_chunk=4, eps=full.norm_eps)
        pre = [Mb.mamba_xproj(p, h) for p in shares]
        summed = sum(xp for _, _, xp in pre)
        np.testing.assert_allclose(
            np.asarray(summed), np.asarray(Mb.mamba_xproj(p_full, h)[2]),
            rtol=1e-5, atol=1e-5)
        parts = [Mb.mamba_from_xproj(p, u, z, summed, h.dtype, 4,
                                     full.norm_eps)
                 for p, (u, z, _) in zip(shares, pre)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def _two_step_gaps(backend: str) -> dict:
    """Largest relative gap of x, y and u after two INTERACT steps of the
    program (``backend``) against the reference, over agents."""
    from repro.core.bilevel import AgentData
    from repro.core.consensus import ring_mixing
    from repro.core.lm_problem import LMBilevelProblem
    from repro.hypergrad import HypergradConfig
    from repro.solvers import SolverConfig, TopologyConfig, make_solver

    m = 4
    cfg = tiny(tp_size=TP)
    prob = LMBilevelProblem(cfg, mu_g=HYPER["mu_g"],
                            lipschitz_g=HYPER["lipschitz_g"], ce_chunk=8)
    hg = HypergradConfig(backend="neumann-linearized",
                         neumann_k=HYPER["neumann_k"],
                         lipschitz_g=HYPER["lipschitz_g"])
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (m, 2, 1, 17), 0, cfg.held_vocab), np.int32)
    data = AgentData(inner_x=ids[:, 0, :, :-1], inner_y=ids[:, 0, :, 1:],
                     outer_x=ids[:, 1, :, :-1], outer_y=ids[:, 1, :, 1:])
    x0, y0 = R.init_point(point_conf(cfg), jax.random.PRNGKey(6))
    solver = make_solver(SolverConfig(
        algo="interact", alpha=ALPHA, beta=BETA, num_agents=m,
        backend=backend, hypergrad=hg,
        backend_opts={"agent_axes": ("data",)} if backend == "ppermute"
        else {}, topology=TopologyConfig(kind="ring")))
    if backend == "ppermute":
        from repro.launch.distributed import MeshRunner
        solver.build(prob, hg, m=m, n=32)
        runner = MeshRunner(solver, m)
        gdata = runner.put(data)
        with jax.set_mesh(runner.mesh):
            point = (x0, y0)
            st = runner.init_fn(point, gdata)(point, gdata)
            for _ in range(2):
                st, _ = runner.run(st, gdata, 1)
    else:
        st = solver.init(None, prob, hg, x0, y0, data)
        for _ in range(2):
            st = solver.step(st, data)
    st = jax.device_get(st)
    batch = lambda k: ((ids[:, 0, :, :-1], ids[:, 0, :, 1:]),
                       (ids[:, 1, :, :-1], ids[:, 1, :, 1:]))
    ref = jax.device_get(R.interact(
        ref_conf(cfg), HYPER, x0, y0, batch, ring_mixing(m).matrix, ALPHA,
        BETA, 2, jax.devices()[:1]))

    out = dict(x=0.0, y=0.0, u=0.0)
    for i in range(m):
        pick = lambda t: jax.tree_util.tree_map(lambda l: l[i], t)
        for k in out:
            out[k] = max(out[k], leaf_gap(pick(getattr(st, k)),
                                          pick(ref[k])))
    return out


@pytest.mark.parametrize("backend", ["dense", "ppermute"])
def test_lm_problem_interact_matches_reference(backend):
    if backend == "dense":
        gaps = _two_step_gaps("dense")
    else:
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=f"{REPO / 'src'}:{REPO / 'tests'}")
        code = ("import json, test_jamba2 as t; "
                "print(json.dumps(t._two_step_gaps('ppermute')))")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        gaps = json.loads(proc.stdout.strip().splitlines()[-1])
    assert all(v < 2e-3 for v in gaps.values()), gaps
