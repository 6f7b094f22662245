"""Readings of the LM ring cell's controls and planted faults against the
plain reference, the reference computed once per seed.

    python3 bench/lm_control.py --workload jamba2-3b.s8k.ring4 --seeds 1 2
    python3 bench/lm_control.py --workload ... --seeds 1 --names matmul_f8

For each seed: the sound program's ``check_steps`` steps and the plain
reference's (``bench/drivers/lm_ring.py``), then each named control or
fault (default: every control) in the program's place, each compared
with the same reference.  Prints one JSON line per reading with each
compared number, its limit and ``correct`` as ``bench/run.py`` decides
it, and the worst leaves of each gap.  A control or a fault has to come
out as not correct; the limits lie between the sound program's readings
and the controls' (PERF.md).  ``--seconds`` first times a window of the
sound program; ``--scopes S`` then traces an S-second window and reports
the device time per step of each layer scope (``repro.mamba``,
``repro.attn``, ``repro.ffn``, ``repro.lm_head``, ``repro.mix``, ...; the
innermost one of an op's path, ``bench/scopes.py``'s trace reading);
``--agents`` runs a ring of fewer agents on as many chips (a per-agent
look on one chip: the mix is then the identity).  ``--names
products_bf16`` reads, on a CPU, the program with the chip's default
products (bf16 inputs, float32 accumulation) emulated.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def worst_leaves(prog: list, ref: list, name: str, count: int,
                 norm) -> list:
    """The ``count`` largest per-leaf gaps of ``name`` over the agents."""
    import jax
    rows = []
    for i, (p, r) in enumerate(zip(prog, ref)):
        pl = jax.tree_util.tree_leaves_with_path(p[name])
        rl = jax.tree_util.tree_leaves(r[name])
        norms = [norm(l) for l in rl]
        floor = float(np.median(norms))
        for (path, a), b, n in zip(pl, rl, norms):
            gap = norm(np.asarray(a, np.float32) - b)
            rows.append([gap / max(n, floor, 1e-30), i,
                         jax.tree_util.keystr(path), n / floor])
    return sorted(rows, reverse=True)[:count]


def scope_split(trace_dir: str, steps: int) -> dict:
    """Device seconds per step of each layer scope in a traced window,
    leaf ops attributed to the innermost scope of their path, averaged
    over the chips; ``busy`` and ``window`` as ``tracereduce`` reads them."""
    import scopes
    import tracereduce as tr
    from repro import tracing
    names = tracing.LAYERS + tracing.LM_LAYERS
    planes = scopes.load_scoped_trace(trace_dir)
    lo, hi, _ = scopes.window_line(planes)
    out = dict.fromkeys(names + (scopes.STEP_OTHER, scopes.NONE), 0.0)
    devices = [lines[tr.OPS_LINE] for p, lines in planes.items()
               if p.startswith(tr.DEVICE_PREFIX) and tr.OPS_LINE in lines]
    for evs in devices:
        for n, s, d, path in evs:
            if s + d <= lo or s >= hi or not scopes.is_leaf(n):
                continue
            comps = scopes.scope_path(path)
            key = scopes.NONE
            if tracing.STEP in comps:
                key = next((c for c in reversed(comps) if c in names),
                           scopes.STEP_OTHER)
            out[key] += (min(s + d, hi) - max(s, lo)) * 1e-9
    red = tr.reduce_trace(scopes.plain(planes))
    per = len(devices) * max(steps, 1)
    return dict({k: v / per for k, v in out.items()}, steps=steps,
                busy_s=red.busy_s, window_s=red.window_s,
                collective_exposed_s=red.collective_exposed_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--names", nargs="*", default=None)
    ap.add_argument("--agents", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--scopes", type=float, default=0.0)
    ap.add_argument("--leaves", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.REPO / "src"))
    run.enable_compile_cache()
    spec = run.load_cell(args.workload)
    conf, params = spec["config"], dict(spec["traffic"]["params"])
    if args.agents:
        params["num_agents"] = args.agents
    devices = run.require_devices(int(params["num_agents"]))
    driver = run.load_module(
        BENCH / "drivers" / f"{spec['traffic']['driver']}.py",
        f"bench_driver_{spec['traffic']['driver']}")
    names = driver.CONTROLS if args.names is None else args.names

    def report(seed, name, prog, ref, extra):
        compared = driver.compare(prog, ref, params["limits"])
        print(json.dumps(dict(
            workload=args.workload, seed=seed, program=name,
            agents=len(devices), correct=all(
                c["value"] <= c["limit"] for c in compared),
            compared={c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in compared},
            worst={g: worst_leaves(prog, ref, g, args.leaves, driver.norm)
                   for g in ("dx", "u")}, **extra)), flush=True)

    for seed in args.seeds:
        t0 = time.perf_counter()
        cell = driver.setup(conf, params, seed, devices)
        extra = {"setup_s": time.perf_counter() - t0}
        if args.seconds:
            w = cell.window(args.seconds)
            extra.update(window_s=w["elapsed_s"], steps=w["agent_steps"],
                         agent_steps_per_s=w["metrics"]["agent_steps_per_s"])
        if args.scopes:
            import tempfile
            import jax
            import tracereduce as tr
            with tempfile.TemporaryDirectory(prefix="lm_scopes_") as tmp:
                jax.profiler.start_trace(tmp)
                try:
                    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                        w = cell.window(args.scopes)
                finally:
                    jax.profiler.stop_trace()
                try:
                    extra["scopes"] = scope_split(
                        tmp, w["agent_steps"] // len(devices))
                except Exception as e:  # a reading lost, not the run
                    extra["scopes_error"] = repr(e)
        extra["memory_peak_bytes"] = run.memory_peak(devices)
        cell.release()
        t0 = time.perf_counter()
        prog = cell.program_updates()
        extra["program_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = cell.reference_updates()
        extra["reference_s"] = time.perf_counter() - t0
        report(seed, "sound", prog, ref, extra)
        del cell
        for name in names:
            t0 = time.perf_counter()
            with driver.planted(name):
                bad = driver.setup(conf, driver.control_params(params, name),
                                   seed, devices)
                bad.release()
                prog = bad.program_updates()
            report(seed, name, prog, ref,
                   {"seconds": time.perf_counter() - t0})
            del bad
    return 0


if __name__ == "__main__":
    sys.exit(main())
