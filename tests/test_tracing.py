"""The program's layer scopes, host spans and compile counter
(``repro.tracing``) at the cut Section-6 sizes of the CPU tests."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import (HypergradConfig, MLPMetaProblem, convergence_metric,
                        init_head, init_mlp_backbone, make_synthetic_agents)
from repro.core import metrics
from repro.solvers import SolverConfig, make_solver

M = 5
OP_NAME = re.compile(r'op_name="([^"]*)"')
COMPONENT = re.compile(r"^(?:[A-Za-z_]\w*\()*(repro\.\w+)\)*$")


@pytest.fixture(scope="module")
def setup():
    data = make_synthetic_agents(jax.random.PRNGKey(0), num_agents=M,
                                 n_per_agent=60, d_in=16, num_classes=5)
    prob = MLPMetaProblem(mu_g=0.5, lipschitz_g=4.0)
    x0 = init_mlp_backbone(jax.random.PRNGKey(1), 16, hidden=20)
    y0 = init_head(jax.random.PRNGKey(2), 20, 5)
    hg = HypergradConfig(method="cg", cg_iters=4)
    return prob, hg, x0, y0, data


def scopes_in(text: str) -> set:
    """The ``repro.*`` path components of a compiled text's op names."""
    return {m.group(1) for name in OP_NAME.findall(text)
            for comp in name.split("/") for m in [COMPONENT.match(comp)] if m}


def strip_metadata(text: str) -> str:
    """The instructions alone: no metadata, no stack-frame tables."""
    text = text[text.index("\n%"):] if "\n%" in text else text
    return re.sub(r", metadata=\{[^}]*\}", "", text)


def compiled_texts(setup, algo="interact", steps=2):
    """``(init, run, eq.-11)`` compiled texts of one solver, traced anew."""
    prob, hg, x0, y0, data = setup
    jax.clear_caches()
    solver = make_solver(SolverConfig(algo=algo, alpha=0.3, beta=0.3,
                                      num_agents=M, batch_size=8))
    state = solver.init(None, prob, hg, x0, y0, data)
    init_txt = jax.jit(lambda: solver.init(None, prob, hg, x0, y0, data)
                       ).lower().compile().as_text()
    run_txt = solver._run_fn.lower(state, data, steps).compile().as_text()
    met_txt = metrics._convergence_metric.lower(
        prob, hg, state.x, state.y, 5, 0.5, data).compile().as_text()
    return init_txt, run_txt, met_txt


@pytest.fixture(scope="module")
def texts(setup):
    return compiled_texts(setup)


def test_compiled_programs_carry_the_six_scopes(texts):
    init_txt, run_txt, met_txt = texts
    assert tracing.INIT in scopes_in(init_txt)
    assert {tracing.STEP, tracing.MIX, tracing.LOCAL_GRAD,
            tracing.HYPERGRAD} <= scopes_in(run_txt)
    assert {tracing.EQ11, tracing.HYPERGRAD} <= scopes_in(met_txt)
    assert tracing.STEP not in scopes_in(met_txt)
    assert set(tracing.PHASES + tracing.LAYERS) <= (
        scopes_in(init_txt) | scopes_in(run_txt) | scopes_in(met_txt))


@pytest.mark.parametrize("algo", ["svr-interact", "gt-dsgd", "d-sgd"])
def test_every_solver_inherits_the_step_scopes(setup, algo):
    init_txt, run_txt, _ = compiled_texts(setup, algo)
    assert tracing.INIT in scopes_in(init_txt)
    assert {tracing.STEP, tracing.MIX} <= scopes_in(run_txt)


def test_hypergrad_scope_sits_inside_local_grad_in_the_step(texts):
    _, run_txt, _ = texts
    for name in OP_NAME.findall(run_txt):
        comps = [m.group(1) for c in name.split("/")
                 for m in [COMPONENT.match(c)] if m]
        if tracing.HYPERGRAD in comps:
            assert comps[:2] == [tracing.STEP, tracing.LOCAL_GRAD], name


def test_scopes_change_no_instruction(setup, texts, monkeypatch):
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_texts(setup)
    assert not scopes_in(bare[1])
    for with_scopes, without in zip(texts, bare):
        assert strip_metadata(with_scopes) == strip_metadata(without)


def test_compile_events_count_retraces_not_cached_calls():
    f = jax.jit(lambda a: jnp.sin(a) * 2.0)
    x = jnp.ones((7,))
    tracing.compile_events()
    before = tracing.compile_events()
    f(x).block_until_ready()
    first = tracing.compile_events()
    assert first >= before + 2      # a jaxpr trace and a backend compile
    f(x).block_until_ready()
    assert tracing.compile_events() == first
    f(jnp.ones((9,))).block_until_ready()   # new shape: traced anew
    assert tracing.compile_events() > first


def test_convergence_metric_wrapper_returns_the_jitted_values(setup):
    prob, hg, x0, y0, data = setup
    solver = make_solver(SolverConfig(algo="interact", alpha=0.3, beta=0.3,
                                      num_agents=M))
    st = solver.run(solver.init(None, prob, hg, x0, y0, data), data, 3)
    rep = convergence_metric(prob, hg, st.x, st.y, 20, 0.5, data)
    direct = metrics._convergence_metric(prob, hg, st.x, st.y, 20, 0.5, data)
    by_name = convergence_metric(prob, hg, st.x, st.y, inner_steps=20,
                                 inner_lr=0.5, data=data)
    fn = jax.jit(metrics.convergence_metric_fn(prob, hg, data, 20, 0.5))
    for a, b, c in zip(rep, direct, by_name):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(fn(st)), np.asarray(rep.total))


def test_host_spans_reach_the_profiler_trace(setup, tmp_path):
    from jax.profiler import ProfileData
    prob, hg, x0, y0, data = setup
    solver = make_solver(SolverConfig(algo="interact", alpha=0.3, beta=0.3,
                                      num_agents=M))
    st = solver.init(None, prob, hg, x0, y0, data)
    st = solver.run(st, data, 2)
    float(convergence_metric(prob, hg, st.x, st.y, 5, 0.5, data).total)
    jax.profiler.start_trace(str(tmp_path))
    try:
        st = solver.run(st, data, 2)
        float(convergence_metric(prob, hg, st.x, st.y, 5, 0.5, data).total)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events}
    assert {tracing.RUN, tracing.EQ11} <= names


def test_names_are_distinct_and_namespaced():
    names = tracing.PHASES + tracing.LAYERS + (tracing.RUN,)
    assert len(set(names)) == len(names)
    assert all(COMPONENT.match(n).group(1) == n for n in names)
    assert all(n.startswith("repro.") for n in names)


@pytest.mark.parametrize("outside", [None, "/elsewhere/cache"])
def test_compile_cache_key_keeps_the_scopes(monkeypatch, outside):
    from repro.launch import compile_cache
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.update({name: value}))
    if outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    assert updates["jax_compilation_cache_include_metadata_in_key"] is True
