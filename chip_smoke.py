"""Smoke run of INTERACT's main path on a TPU chip.

    python3 chip_smoke.py                # one v5e chip
    python3 chip_smoke.py --four-chips   # the cross-chip path, four chips

One chip:
  1. device   — names the device; anything but a TPU is a failure.
  2. section6 — the paper's Section-6 instance (m = 10 agents, Erdős–Rényi
                graph) through ``repro.solvers.solve`` for INTERACT and
                SVR-INTERACT on the ``dense`` and ``pallas`` (compiled
                kernel) consensus backends.  The eq.-11 stationarity must
                fall, dense and pallas must agree on the chip, and the chip
                must agree with the same dense solve on the host CPU.
  3. trainer  — ``repro.launch.train`` on smollm-360m at full width, one
                agent, batch 4 x 256 tokens, 5 steps; ``outer_ce`` must be
                finite at every step.

Four chips (``--four-chips``), one agent per chip:
  2. section6 on the mesh — ``run_section6`` with the ``ppermute`` backend
                against the single-device ``dense`` solve of that config.
  3. trainer  — the same full-width trainer with 4 agents on the (4, 1)
                mesh; the final state must keep the gradient-tracking
                invariant mean_i u_i = mean_i p_i, which holds only if the
                cross-chip ppermute mix is doubly stochastic.

Everything runs in this one process (a chip belongs to one process).  A
phase that fails is reported and the remaining phases still run; the
script then exits nonzero without the result line.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Section 6 on one chip: steps, metric cadence, agent count.
S6_STEPS = 300
S6_RECORD_EVERY = 100
S6_AGENTS = 10
# dense vs pallas on the chip: the same f32 algorithm, two mixing
# implementations.
BACKEND_RTOL = 1e-3
# chip vs host CPU: the same f32 program on two backends.  The mix and the
# problem's matmuls run at HIGHEST precision; at the TPU's default (bf16
# operands) the chip's stationarity floored 8x above the CPU's.
CPU_RTOL = 0.10
# --four-chips: mesh ppermute vs single-device dense, same config.
MESH_RTOL = 5e-3
# --four-chips: ||mean_i u_i - mean_i p_i|| / ||mean_i p_i|| on the bf16
# trainer state.  A mix that is not doubly stochastic moves the mean by
# O(1); bf16 rounding of five tracking updates stays far below this.
TRACKING_RTOL = 2e-2

TRAINER_ARGV = ["--arch", "smollm-360m", "--per-agent-batch", "4",
                "--seq-len", "256", "--steps", "5", "--log-every", "1"]


class PhaseFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailure(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def device_phase(need: int):
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform!r}")
    check(len(devs) >= need, f"needs {need} chips, found {len(devs)}")
    return dev, len(devs)


def section6_phase(steps: int = S6_STEPS, record_every: int = S6_RECORD_EVERY,
                   agents: int = S6_AGENTS) -> None:
    import jax
    from repro.solvers import SolverConfig, TopologyConfig, solve

    cpu = jax.devices("cpu")[0]
    problems = []

    def expect(cond, msg):
        if not cond:
            problems.append(msg)

    for algo in ("interact", "svr-interact"):
        finals = {}
        for backend in ("dense", "pallas"):
            cfg = SolverConfig(algo=algo, num_agents=agents, backend=backend,
                               topology=TopologyConfig(kind="erdos-renyi"))
            t0 = time.perf_counter()
            res = solve(cfg, steps, record_every)
            first, last = res.trace[0], res.trace[-1]
            print(f"[section6] {algo:12s} {backend:6s} stationarity "
                  f"{first:.6g} -> {last:.6g} over {steps} steps "
                  f"({time.perf_counter() - t0:.1f}s incl. compile)",
                  flush=True)
            expect(all(math.isfinite(v) for v in res.trace),
                   f"{algo}/{backend}: non-finite metric {res.trace}")
            expect(last < first, f"{algo}/{backend}: stationarity did not "
                                 f"fall ({first} -> {last})")
            finals[backend] = last
        d = rel_diff(finals["pallas"], finals["dense"])
        print(f"[section6] {algo:12s} pallas vs dense on chip: rel diff "
              f"{d:.3e} (limit {BACKEND_RTOL:g})", flush=True)
        expect(d <= BACKEND_RTOL, f"{algo}: pallas and dense disagree ({d})")

        cfg = SolverConfig(algo=algo, num_agents=agents, backend="dense",
                           topology=TopologyConfig(kind="erdos-renyi"))
        with jax.default_device(cpu):
            ref = solve(cfg, steps, record_every).trace[-1]
        d = rel_diff(finals["dense"], ref)
        print(f"[section6] {algo:12s} chip {finals['dense']:.6g} vs host "
              f"CPU {ref:.6g}: rel diff {d:.3e} (limit {CPU_RTOL:g})",
              flush=True)
        expect(d <= CPU_RTOL, f"{algo}: chip and CPU disagree ({d})")
    check(not problems, "; ".join(problems))


def mesh_section6_phase(agents: int = 4) -> None:
    from repro.core import convergence_metric
    from repro.launch.distributed import run_section6
    from repro.solvers import SolverConfig, solve
    from repro.solvers.api import default_setup

    steps, every, inner_steps, inner_lr = 30, 10, 120, 0.5
    setup = dict(n_per_agent=80, d_in=8, hidden=8, classes=3)
    res = run_section6(num_agents=agents, backend="ppermute",
                       num_steps=steps, record_every=every,
                       metric_inner_steps=inner_steps,
                       metric_inner_lr=inner_lr, **setup)
    print(f"[section6-mesh] ppermute on mesh {res['mesh_shape']}: "
          f"stationarity {res['trace'][0]:.6g} -> {res['final_metric']:.6g}",
          flush=True)

    problem, x0, y0, data = default_setup(0, num_agents=agents, **setup)
    cfg = SolverConfig(algo="interact", alpha=0.1, beta=0.1,
                       num_agents=agents, backend="dense", seed=0)

    def metric(st):
        return float(convergence_metric(problem, cfg.hypergrad, st.x, st.y,
                                        inner_steps, inner_lr, data).total)

    ref = solve(cfg, steps, every, problem=problem, x0=x0, y0=y0,
                data=data, metric_fn=metric)
    d = rel_diff(res["final_metric"], ref.trace[-1])
    print(f"[section6-mesh] single-device dense {ref.trace[-1]:.6g}: rel "
          f"diff {d:.3e} (limit {MESH_RTOL:g})", flush=True)
    check(res["final_metric"] < res["trace"][0],
          "mesh run: stationarity did not fall")
    check(d <= MESH_RTOL, f"mesh ppermute and dense disagree ({d})")


def trainer_phase(agents: int, extra_argv=()) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch import train

    run = train.main(TRAINER_ARGV + ["--agents", str(agents),
                                     *extra_argv])
    ces = [e["outer_ce"] for e in run.log]
    check(len(ces) == 5, f"expected 5 logged steps, got {len(ces)}")
    check(all(math.isfinite(c) for c in ces), f"non-finite outer_ce {ces}")
    print(f"[trainer] outer_ce {' '.join(f'{c:.4f}' for c in ces)}; "
          f"fell over the steps: {ces[-1] < ces[0]}", flush=True)
    warm = [e["s_per_step"] for e in run.log[1:]]
    print(f"[trainer] smoke timing, not a metric: first step (compile) "
          f"{run.log[0]['s_per_step']:.2f}s, then "
          f"{sum(warm) / len(warm):.3f} s/step", flush=True)

    if agents > 1:
        def agent_mean(tree):
            return [jnp.mean(l.astype(jnp.float32), axis=0)
                    for l in jax.tree_util.tree_leaves(tree)]

        mu, mp = agent_mean(run.state.u), agent_mean(run.state.p_prev)
        num = math.sqrt(sum(float(jnp.sum((a - b) ** 2))
                            for a, b in zip(mu, mp)))
        den = math.sqrt(sum(float(jnp.sum(b ** 2)) for b in mp))
        d = num / max(den, 1e-30)
        print(f"[trainer] tracking invariant |mean u - mean p| / |mean p| "
              f"= {d:.3e} (limit {TRACKING_RTOL:g})", flush=True)
        check(d <= TRACKING_RTOL, f"gradient tracking broken ({d})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip path, one agent per chip")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: {src / 'repro'} not found — run from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    # The host-CPU reference needs the CPU backend next to the TPU one.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"[setup] compilation cache: {cache}", flush=True)

    need = 4 if args.four_chips else 1
    try:
        dev, count = device_phase(need)
    except PhaseFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if args.four_chips:
        phases = [("section6-mesh", lambda: mesh_section6_phase(agents=4)),
                  ("trainer", lambda: trainer_phase(agents=4))]
    else:
        phases = [("section6", section6_phase),
                  ("trainer", lambda: trainer_phase(agents=1))]
    failed = []
    for name, run in phases:
        # Phase boundary: report the failure, still run the other phases,
        # and exit nonzero at the end.
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
