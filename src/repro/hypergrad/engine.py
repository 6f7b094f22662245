"""The `HypergradEngine` API: one pluggable backend behind eq. (5)/(22).

Every algorithm obtains its outer gradient through the approximate
hypergradient of eq. (5),

    grad_bar f(x, y) = grad_x f(x, y)
        - H_xy(g)(x, y) [H_yy(g)(x, y)]^{-1} grad_y f(x, y),

and the whole per-step cost is dominated by how the inverse is applied.
A ``HypergradEngine`` owns exactly that piece — ``solve(...)`` returns
``z ~= [H_yy g]^{-1} grad_y f`` plus measured evaluation counts — while
the shared ``hypergradient`` surface owns the invariant parts (the joint
grad of f, the single H_xy cross term, the final subtraction), so every
backend is interchangeable and bit-comparable.

Backends (see ``available_backends`` / docs/HYPERGRAD.md):

    cg                  seed CG, fixed trip count, per-matvec HVP —
                        the correctness oracle (bit-compatible).
    cg-linearized       ``jax.linearize`` once, flat-space CG with an
                        early-exit ``while_loop`` at relative tolerance.
    neumann             seed eq.-(22) chain; the stochastic form now runs
                        a dynamic k-trip loop (expected (K-1)/2 HVPs).
    neumann-linearized  linearize-once replay of the product chain.
    cholesky            materialise H_yy (small heads), factor once,
                        ``cho_solve`` — exact to solver precision.

Mirrors the ``ConsensusEngine`` / ``@register_solver`` registries of
PRs 1-2: adding a backend is one ``@register_backend`` class.
"""
from __future__ import annotations

from typing import Callable

import jax

from repro import tracing
from repro.hypergrad.config import HypergradConfig
from repro.hypergrad.operator import HypergradStats, flat_dot, tree_sub

__all__ = [
    "HypergradEngine",
    "available_backends",
    "get_backend",
    "register_backend",
    "hvp_yy",
    "hvp_xy",
    "hypergradient",
    "hypergradient_with_stats",
    "local_gradients",
    "measure_counts",
    "measure_problem_counts",
]


def hvp_yy(g: Callable, x, y, v, *args):
    """H_yy(g)(x, y) @ v via forward-over-reverse."""
    grad_y = lambda yy: jax.grad(g, argnums=1)(x, yy, *args)
    return jax.jvp(grad_y, (y,), (v,))[1]


def hvp_xy(g: Callable, x, y, v, *args):
    """H_xy(g)(x, y) @ v  =  grad_x <grad_y g(x, y), v>."""
    def inner(xx):
        gy = jax.grad(g, argnums=1)(xx, y, *args)
        return flat_dot(gy, v)

    return jax.grad(inner)(x)


class HypergradEngine:
    """Base class: apply the inner-Hessian inverse, counting evaluations.

    ``solve`` returns ``(z, stats)`` where ``z ~= [H_yy g]^{-1} b`` and
    ``stats`` counts only the solve's own evaluations (the shared
    ``hypergradient`` surface adds the H_xy cross term and the grad-f
    pass).  ``inner_hess_yy`` is an optional problem-provided closed form
    for the flat H_yy (see ``repro.core.bilevel.BilevelProblem``); only
    the cholesky backend consumes it.
    """

    name = "base"

    def solve(self, g: Callable, x, y, b, cfg: HypergradConfig,
              g_args: tuple, key, inner_hess_yy: Callable | None = None):
        raise NotImplementedError

    def local_gradients(self, problem, x, y, cfg: HypergradConfig,
                        f_batch, g_batch, key):
        """``(p, v, stats)`` of one agent: eq. (5) through ``solve`` and
        grad_y g.  A backend that can use more of the problem's structure
        than its losses overrides this."""
        p, stats = hypergradient_with_stats(
            problem.outer, problem.inner, x, y, cfg, f_args=(f_batch,),
            g_args=(g_batch,), key=key, inner_hess_yy=problem.inner_hess_yy)
        v = jax.grad(problem.inner, argnums=1)(x, y, g_batch)
        return p, v, stats


_REGISTRY: dict[str, HypergradEngine] = {}


def register_backend(name: str) -> Callable[[type], type]:
    """Class decorator: register a (stateless) engine under ``name``."""

    def deco(cls: type) -> type:
        existing = _REGISTRY.get(name)
        if existing is not None and type(existing) is not cls:
            raise ValueError(f"hypergradient backend {name!r} already "
                             f"registered ({type(existing).__name__})")
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def _populate() -> None:
    # Engines live in sibling modules; importing them registers them.
    from repro.hypergrad import cg as _cg            # noqa: F401
    from repro.hypergrad import cholesky as _chol    # noqa: F401
    from repro.hypergrad import neumann as _neu      # noqa: F401


def available_backends() -> tuple[str, ...]:
    """Registered backend names, sorted."""
    _populate()
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> HypergradEngine:
    """Look a backend up by registry name."""
    _populate()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown hypergradient backend {name!r}; "
            f"choose from {tuple(sorted(_REGISTRY))}") from None


def hypergradient_with_stats(
    f: Callable,
    g: Callable,
    x,
    y,
    cfg: HypergradConfig,
    f_args: tuple = (),
    g_args: tuple = (),
    key: jax.Array | None = None,
    inner_hess_yy: Callable | None = None,
):
    """grad_bar f(x, y) of eq. (5)/(22) plus measured evaluation counts.

    ``f(x, y, *f_args)`` is the outer loss, ``g(x, y, *g_args)`` the inner
    (mu_g-strongly-convex in y).  Returns ``(p, HypergradStats)`` where
    ``p`` is a pytree like x and the stats count this call's gradient /
    HVP / Hessian evaluations (Definition-1 accounting, measured inside
    the trace — see docs/HYPERGRAD.md).
    """
    engine = get_backend(cfg.resolve_backend())
    gx, gy = jax.grad(f, argnums=(0, 1))(x, y, *f_args)
    with tracing.scope(tracing.HYPERGRAD):
        z, stats = engine.solve(g, x, y, gy, cfg, g_args, key,
                                inner_hess_yy)
    correction = hvp_xy(g, x, y, z, *g_args)
    p = tree_sub(gx, correction)
    stats = stats._replace(hvp_count=stats.hvp_count + 1,   # H_xy cross term
                           grad_count=stats.grad_count + 1)  # grad_{x,y} f
    return p, stats


def hypergradient(
    f: Callable,
    g: Callable,
    x,
    y,
    cfg: HypergradConfig,
    f_args: tuple = (),
    g_args: tuple = (),
    key: jax.Array | None = None,
    inner_hess_yy: Callable | None = None,
):
    """The approximate hypergradient grad_bar f(x, y) of eq. (5)/(22).

    Same contract as the historical ``repro.core.hypergrad.hypergradient``
    (bit-compatible for the ``cg`` / ``neumann`` reference backends at
    identical configs); ``hypergradient_with_stats`` additionally returns
    the measured evaluation counts.
    """
    p, _ = hypergradient_with_stats(f, g, x, y, cfg, f_args=f_args,
                                    g_args=g_args, key=key,
                                    inner_hess_yy=inner_hess_yy)
    return p


def local_gradients(problem, cfg: HypergradConfig, x, y, f_batch, g_batch,
                    key: jax.Array | None = None):
    """``(p, v, stats)``: one agent's hypergradient of eq. (5), its inner
    gradient grad_y g, and the counted evaluations, from the backend
    ``cfg`` selects.  ``problem`` is a ``repro.core.bilevel.BilevelProblem``;
    ``f_batch`` / ``g_batch`` are its outer / inner batches.  The
    ``neumann-linearized`` backend runs the series in head space when the
    problem carries a ``HeadSplit`` (``repro.hypergrad.head``)."""
    engine = get_backend(cfg.resolve_backend())
    return engine.local_gradients(problem, x, y, cfg, f_batch, g_batch, key)


def measure_counts(
    f: Callable,
    g: Callable,
    x,
    y,
    cfg: HypergradConfig,
    f_args: tuple = (),
    g_args: tuple = (),
    key: jax.Array | None = None,
    inner_hess_yy: Callable | None = None,
) -> HypergradStats:
    """Run one hypergradient call and return its counts as python ints.

    This *executes* the estimator (so data-dependent trip counts — the
    early-exit CG, the stochastic-k Neumann chain — report what actually
    ran); ``solve`` and the bench harness use it to attach measured
    per-step ``hvp_count`` / ``grad_count`` to their results.

    For a stochastic-k config with no explicit ``key``, the sampled trip
    count is averaged over a small fixed key set (rounded), so the
    reported cost reflects the estimator's expected (K-1)/2 HVPs rather
    than one arbitrary draw; pass a ``key`` to measure a single draw.
    """
    def one(k):
        _, stats = hypergradient_with_stats(f, g, x, y, cfg, f_args=f_args,
                                            g_args=g_args, key=k,
                                            inner_hess_yy=inner_hess_yy)
        return stats

    if cfg.stochastic_k and key is None:
        samples = [one(jax.random.PRNGKey(s)) for s in range(16)]
        mean = lambda field: round(
            sum(int(getattr(s, field)) for s in samples) / len(samples))
        return HypergradStats(hvp_count=mean("hvp_count"),
                              grad_count=mean("grad_count"),
                              hess_count=mean("hess_count"))
    stats = one(key)
    return HypergradStats(hvp_count=int(stats.hvp_count),
                          grad_count=int(stats.grad_count),
                          hess_count=int(stats.hess_count))


def measure_problem_counts(problem, cfg: HypergradConfig, x0, y0, data,
                           agent: int = 0,
                           key: jax.Array | None = None) -> HypergradStats:
    """``measure_counts`` on one agent's slice of stacked ``AgentData``.

    ``problem`` is any object with ``outer`` / ``inner`` losses and an
    optional ``inner_hess_yy`` (``repro.core.bilevel.BilevelProblem``);
    the shared convention used by ``solve``, the bench harness, and the
    examples to attach measured per-call accounting.
    """
    return measure_counts(
        problem.outer, problem.inner, x0, y0, cfg,
        f_args=((data.outer_x[agent], data.outer_y[agent]),),
        g_args=((data.inner_x[agent], data.inner_y[agent]),),
        key=key, inner_hess_yy=getattr(problem, "inner_hess_yy", None))
