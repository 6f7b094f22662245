"""Closed loop of Section-6 solves, each run to a stationarity target.

A user's solve: initialise INTERACT on a fresh instance, step it in blocks
of ``check_every`` iterations through the solver's scan-compiled
``run``, and read the eq.-11 metric back after each block (one host sync
per block), until the metric is at most ``epsilon``.  A solve still above
it after ``max_steps`` is failed.  Solves follow each other with no pause
for ``--seconds`` seconds, and then on to the end of the pass through
the pool under way.

Traffic: a fixed pool of ``pool`` instances (keys from ``pool_seed``),
visited in passes, each pass the whole pool in an order drawn from
``--seed``.  The window ends on a whole pass, so every seed brings the
same solves in another order: the instances need from 150 to 275
iterations each, and a window that ended inside a pass would let the
seed choose which of them it measures.

End-to-end: ``time_to_gap_s`` (seconds per completed solve) and
``agent_steps_per_s`` (agents x iterations over the window).  The
comparison re-solves a sample of the window's solves, drawn from the seed
and with the longest among them, with the plain reference and compares
the metric after every block and the final iterates.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import numpy as np

import section6_traffic as traffic
from reference import s6_interact as reference

span = jax.profiler.TraceAnnotation


def setup(conf: dict, params: dict, seed: int, devices):
    return ClosedLoop(conf, params, seed)


def leaf_gap(prog, ref) -> float:
    """Worst leaf of ||prog - ref|| over max(||ref leaf||, median leaf)."""
    p_leaves = [np.asarray(l, np.float64)
                for l in jax.tree_util.tree_leaves(prog)]
    r_leaves = [np.asarray(l, np.float64)
                for l in jax.tree_util.tree_leaves(ref)]
    norms = [float(np.linalg.norm(r)) for r in r_leaves]
    floor = float(np.median(norms))
    return max(float(np.linalg.norm(p - r)) / max(n, floor, 1e-30)
               for p, r, n in zip(p_leaves, r_leaves, norms))


def trace_gap(prog, ref) -> float:
    """Largest relative gap of two metric traces, block by block."""
    return max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(prog, ref))


def make_pool(conf: dict, params: dict, seed: int):
    """The pool's instances and the order ``seed`` visits them in."""
    m = int(params["num_agents"])
    make = jax.jit(lambda k: traffic.instance(k, m, conf))
    base = traffic.seed_key(params["pool_seed"])
    pool = [make(jax.random.fold_in(base, j))
            for j in range(int(params["pool"]))]
    rng = np.random.default_rng(seed)
    order = np.concatenate([rng.permutation(len(pool)) for _ in range(256)])
    return pool, order


def compare(conf, params, sample) -> list:
    """Re-solve each sampled solve with the reference at ``highest``;
    the worst metric and iterate gaps over the sample."""
    m = int(params["num_agents"])
    mat = traffic.erdos_renyi_mixing(m, conf["p_connect"],
                                     conf["topology_seed"])
    m_gap = s_gap = 0.0
    for s in sample:
        data, x0, y0 = s["instance"]
        x, y, trace = reference.solve(conf, "highest", data, x0, y0, mat,
                                      int(params["check_every"]),
                                      len(s["trace"]))
        m_gap = max(m_gap, trace_gap(s["trace"], trace))
        s_gap = max(s_gap, leaf_gap((s["x"], s["y"]), (x, y)))
    lim = params["limits"]
    return [dict(name="metric_gap", value=m_gap, limit=lim["metric_gap"]),
            dict(name="iterate_gap", value=s_gap,
                 limit=lim["iterate_gap"])]


def control(conf: dict, params: dict, seed: int, devices) -> list:
    """The reference with three-pass bfloat16 products in the program's
    place: solve the seed's first instances to the target with it, and
    compare those solves against the reference at ``highest``."""
    pool, order = make_pool(conf, params, seed)
    m = int(params["num_agents"])
    every, eps = int(params["check_every"]), float(params["epsilon"])
    mat = traffic.erdos_renyi_mixing(m, conf["p_connect"],
                                     conf["topology_seed"])
    sample = []
    for j in order[:int(params["sample_solves"]) + 1]:
        data, x0, y0 = pool[int(j)]
        x, y, trace = reference.solve(conf, "bf16x3", data, x0, y0, mat,
                                      every, int(params["max_steps"]) // every,
                                      stop_below=eps)
        sample.append(dict(instance=pool[int(j)], trace=trace, x=x, y=y))
    return compare(conf, params, sample)


class ClosedLoop:

    def __init__(self, conf: dict, params: dict, seed: int):
        from repro.core import MLPMetaProblem, convergence_metric
        from repro.core.bilevel import AgentData
        from repro.core.interact import init_state
        from repro.hypergrad import HypergradConfig
        from repro.solvers import SolverConfig, TopologyConfig, make_solver

        self.conf, self.params, self.seed = conf, params, seed
        m = self.m = int(params["num_agents"])
        self.every = int(params["check_every"])
        self.eps = float(params["epsilon"])
        self.max_steps = int(params["max_steps"])

        self.pool, self.order = make_pool(conf, params, seed)

        self.hg = HypergradConfig(**conf["hypergrad"])
        self.problem = MLPMetaProblem(mu_g=conf["mu_g"],
                                      lipschitz_g=conf["lipschitz_g"])
        cfg = SolverConfig(
            algo=params["algo"], alpha=conf["alpha"], beta=conf["beta"],
            num_agents=m, backend=params["backend"], hypergrad=self.hg,
            topology=TopologyConfig(kind=conf["topology"],
                                    p_connect=conf["p_connect"],
                                    seed=conf["topology_seed"]))
        self.solver = make_solver(cfg)
        (ix, iy, ox, oy), _, _ = self.pool[0]
        self.solver.build(self.problem, self.hg, m=m,
                          n=ix.shape[1] + ox.shape[1])
        self.init_fn = jax.jit(partial(init_state, self.problem, self.hg))
        met = conf["metric"]
        self.metric = lambda st, d: convergence_metric(
            self.problem, self.hg, st.x, st.y, int(met["inner_steps"]),
            float(met["inner_lr"]), d).total
        self.inputs = [(AgentData(*d), x0, y0) for d, x0, y0 in self.pool]
        # warm every program of the window: init, one block, the metric
        data, x0, y0 = self.inputs[0]
        st = self.solver.run(self.init_fn(x0, y0, data), data, self.every)
        float(self.metric(st, data))
        self.solves = []

    def solve_one(self, j: int) -> dict:
        data, x0, y0 = self.inputs[j]
        t0 = time.perf_counter()
        with span("bench.s6.init"):
            st = self.init_fn(x0, y0, data)
        steps, trace = 0, []
        while True:
            with span("bench.s6.steps"):
                st = self.solver.run(st, data, self.every)
            steps += self.every
            with span("bench.s6.check"):
                val = float(self.metric(st, data))
            trace.append(val)
            if val <= self.eps or steps >= self.max_steps:
                break
        return dict(j=j, steps=steps, trace=trace, ok=val <= self.eps,
                    seconds=time.perf_counter() - t0, x=st.x, y=st.y)

    def window(self, seconds: float) -> dict:
        solves = []
        pool_size = len(self.pool)
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < seconds
               or len(solves) % pool_size):
            with span("bench.s6.solve"):
                solves.append(self.solve_one(
                    int(self.order[len(solves) % len(self.order)])))
        elapsed = time.perf_counter() - t0
        self.solves += solves
        done = [s for s in solves if s["ok"]]
        metrics = {"agent_steps_per_s":
                   self.m * sum(s["steps"] for s in solves) / elapsed}
        if done:
            metrics["time_to_gap_s"] = (sum(s["seconds"] for s in done)
                                        / len(done))
        return dict(metrics=metrics, attempted=len(solves),
                    failed=len(solves) - len(done), elapsed_s=elapsed,
                    agent_steps=self.m * sum(s["steps"] for s in solves))

    def counters(self) -> dict:
        from repro.hypergrad import measure_problem_counts
        data, x0, y0 = self.inputs[0]
        per_call = measure_problem_counts(self.problem, self.hg, x0, y0,
                                          data)
        n = data.inner_x.shape[1] + data.outer_x.shape[1]
        return {"hvp_per_step": per_call.hvp_count
                * self.solver.hypergrad_calls_per_step(n)}

    def release(self) -> None:
        """Keep the sampled solves' answers on the host; drop the rest."""
        rng = np.random.default_rng([self.seed, 1])
        done = [s for s in self.solves if s["ok"]] or self.solves
        longest = max(range(len(done)), key=lambda i: done[i]["steps"])
        k = min(int(self.params["sample_solves"]), len(done))
        pick = {longest, *rng.choice(len(done), size=k, replace=False)}
        self.sample = [dict(done[i], x=jax.device_get(done[i]["x"]),
                            y=jax.device_get(done[i]["y"]),
                            instance=self.pool[done[i]["j"]])
                       for i in sorted(pick)]
        self.solves = self.inputs = self.solver = None

    def check(self) -> list:
        """Re-solve the sample with the reference; worst gaps over it."""
        return compare(self.conf, self.params, self.sample)
