"""Hessian-vector products per agent and iteration, as the program counts
them (``repro.hypergrad.measure_problem_counts`` times the algorithm's
hypergradient calls per step)."""


def read(ctx):
    return ctx["counters"].get("hvp_per_step")
