"""Faults planted under a cell's timed path, to show that ``correct``
catches them.

Each fault replaces one function of the program for the length of a
``with planted(driver, name):`` block and restores it afterwards:

* ``stale_state``: the step returns its state unchanged;
* ``half_batch``: the gradients see half of each agent's batch, the mean
  taken over the rest;
* ``no_exchange``: the consensus mix leaves every agent's own value;
* ``altered_answer``: the value the program reports back (the eq.-11
  metric of a solve) is off by 2%.

``bench/tests`` drives a run with each fault a cell can have;
``bench/control.py --fault`` reads them on the chip.

``CONTROLS`` holds the program's own lower-precision paths, planted the
same way (``bench/control.py --program``): ``matmul_high`` runs the
Section-6 problem's products at ``HIGH`` (three bfloat16 passes) where
the configuration states ``HIGHEST``; ``matmul_default`` at one pass.
"""
from __future__ import annotations

import contextlib


def _s6_faults():
    import repro.core
    import repro.core.interact as interact
    import repro.solvers.api as api
    from repro.consensus.dense import DenseEngine

    def half(data):
        inner, outer = orig_batch(data)
        cut = lambda b: tuple(a[:, :a.shape[1] // 2] for a in b)
        return cut(inner), cut(outer)

    def altered(*a, **k):
        rep = orig_metric(*a, **k)
        return rep._replace(total=rep.total * 0.98)

    orig_batch = interact._per_agent_batch
    orig_metric = repro.core.convergence_metric
    return {
        "stale_state": [(api.SolverBase, "run",
                         lambda self, state, data, n, **kw: state)],
        "half_batch": [(interact, "_per_agent_batch", half)],
        "no_exchange": [(DenseEngine, "mix", lambda self, tree, **kw: tree)],
        "altered_answer": [(repro.core, "convergence_metric", altered)],
    }


def _s6_controls():
    import jax
    import jax.numpy as jnp
    import repro.core.bilevel as bilevel

    def at(prec):
        return lambda a, b: jnp.matmul(a, b, precision=prec)

    return {
        "matmul_high": [(bilevel, "_dot", at(jax.lax.Precision.HIGH))],
        "matmul_default": [(bilevel, "_dot", at(jax.lax.Precision.DEFAULT))],
    }


FAULTS = {"s6_closed_loop": _s6_faults}
CONTROLS = {"s6_closed_loop": _s6_controls}


def names(driver: str) -> list:
    return sorted(FAULTS[driver]())


@contextlib.contextmanager
def planted(driver: str, name: str):
    table = FAULTS[driver]()
    if driver in CONTROLS:
        table.update(CONTROLS[driver]())
    patches = table[name]
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    try:
        for obj, attr, new in patches:
            setattr(obj, attr, new)
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
