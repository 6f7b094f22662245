"""Hessian-vector products per agent step as the program counts them
(``HypergradStats`` of ``repro.hypergrad.local_gradients``, whose
``neumann-linearized`` backend runs in head space for this problem: the
K-1 head-space products and the H_xy cross term)."""


def read(ctx):
    return ctx["counters"].get("hvp_per_step")
