"""Hypergradient engines: one `hypergradient(...)` surface, five backends.

The per-step cost of every INTERACT variant is dominated by the
hypergradient of eq. (5)/(22); this package makes the inverse application
pluggable (mirroring ``repro.consensus`` / ``repro.solvers``) and makes
its cost *measured*: every matvec flows through a counted
``LinearOperator`` and ``hypergradient_with_stats`` returns per-call
``hvp_count`` / ``grad_count`` / ``hess_count``.

    from repro.hypergrad import HypergradConfig, hypergradient

    cfg = HypergradConfig(backend="cg-linearized")       # or "cholesky", ...
    p = hypergradient(f, g, x, y, cfg, f_args=(fb,), g_args=(gb,))

Backends: ``cg`` / ``neumann`` (seed references, bit-compatible),
``cg-linearized`` / ``neumann-linearized`` (linearize-once replay, flat
space, early exit / dynamic trip count), ``cholesky`` (materialise the
small-head H_yy, factor once).  See docs/HYPERGRAD.md.

``repro.core.hypergrad`` remains as a deprecation shim over this package.
"""
from repro.hypergrad.config import HypergradConfig
from repro.hypergrad.engine import (
    HypergradEngine,
    available_backends,
    get_backend,
    hvp_xy,
    hvp_yy,
    hypergradient,
    hypergradient_with_stats,
    local_gradients,
    measure_counts,
    measure_problem_counts,
    register_backend,
)
from repro.hypergrad.operator import HypergradStats, LinearOperator
from repro.hypergrad.cg import CgInfo, cg_solve
from repro.hypergrad.neumann import (
    neumann_inverse_apply,
    neumann_stochastic_apply,
    neumann_truncated_apply,
)

__all__ = [
    "CgInfo",
    "HypergradConfig",
    "HypergradEngine",
    "HypergradStats",
    "LinearOperator",
    "available_backends",
    "cg_solve",
    "get_backend",
    "hvp_xy",
    "hvp_yy",
    "hypergradient",
    "hypergradient_with_stats",
    "local_gradients",
    "measure_counts",
    "measure_problem_counts",
    "neumann_inverse_apply",
    "neumann_stochastic_apply",
    "neumann_truncated_apply",
    "register_backend",
]
