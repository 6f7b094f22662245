"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  fig2  convergence of INTERACT/SVR-INTERACT vs GT-DSGD/D-SGD (5/10 agents)
  fig4  edge-connectivity sensitivity
  fig5  learning-rate sensitivity
  table1 sample & communication complexity to eps-stationarity
  compression  compressor x interval wire-bytes-per-stationarity sweep
         (+ BENCH_compression.json dump, see benchmarks.check_gates)
  hypergrad  HypergradEngine backend sweep (+ BENCH_hypergrad.json dump)
  kernels  Pallas kernel micro-structure
  topology  time-varying topology: stationarity + wire bytes vs link
         failure, gossip vs static at matched bandwidth
         (+ BENCH_topology.json dump, see benchmarks.check_gates)
  byzantine  Byzantine resilience: stationarity vs attacker count per
         combine rule, guard time-to-detection
         (+ BENCH_byzantine.json dump, see benchmarks.check_gates)
  resilience  fault tolerance: kill/resume bitwise parity, checkpoint
         overhead, chaos-campaign recovery
         (+ BENCH_resilience.json dump, see benchmarks.check_gates)
  distributed  real multi-process launches (scripts/launch_local.py):
         measured vs priced bytes-on-wire per compressor, 1-process
         bitwise parity with/without the distributed runtime, 2-process
         matched stationarity, round latency
         (+ BENCH_distributed.json dump, see benchmarks.check_gates)
  roofline dry-run derived roofline terms (if dry-run artifacts exist)

The figure suites (fig2/fig4/fig5) run their seed x config grids through
the batched sweep engine (``repro.solvers.sweep``, docs/SWEEPS.md) —
one compiled vmapped program per algo/topology group — and share one
``BENCH_sweep.json`` dump (``$BENCH_JSON_DIR`` or cwd) whose headline
``vmap_speedup`` / ``scan_speedup`` / ``trace_bitwise_match`` fields the
bench-smoke CI job asserts on, so batching regressions fail the build.

``--smoke`` runs every suite at CI-sized iteration counts (used by the
bench-smoke CI job to keep the harness from rotting against API changes):

    PYTHONPATH=src python -m benchmarks.run --smoke

The harness runs each suite in its own subprocess so results stay
bitwise-identical to standalone runs (``--suite NAME`` runs one suite
in-process; that is what the children execute).
"""
from __future__ import annotations

import argparse
import sys
import traceback


SUITE_NAMES = ("fig2", "fig4", "fig5", "table1", "compression",
               "hypergrad", "kernels", "topology", "byzantine",
               "resilience", "distributed", "roofline")


def _suite_fn(name: str):
    from benchmarks import (bench_byzantine, bench_complexity,
                            bench_compression, bench_connectivity,
                            bench_convergence, bench_distributed,
                            bench_hypergrad, bench_kernels, bench_lr,
                            bench_resilience, bench_topology,
                            roofline_report)
    return {
        "fig2": bench_convergence.run,
        "fig4": bench_connectivity.run,
        "fig5": bench_lr.run,
        "table1": bench_complexity.run,
        "compression": bench_compression.run,
        "hypergrad": bench_hypergrad.run,
        "kernels": bench_kernels.run,
        "topology": bench_topology.run,
        "byzantine": bench_byzantine.run,
        "resilience": bench_resilience.run,
        "distributed": bench_distributed.run,
        "roofline": roofline_report.run,
    }[name]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-iteration run of every suite (CI)")
    ap.add_argument("--suite", choices=SUITE_NAMES, default=None,
                    help="run a single suite in-process (the full "
                         "harness spawns one such child per suite)")
    args = ap.parse_args()

    if args.suite is not None:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        fn = _suite_fn(args.suite)
        try:
            for row in fn(smoke=args.smoke):
                print(row.csv(), flush=True)
        except Exception:
            print(f"{args.suite},0.0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
            raise SystemExit(1)
        return

    # Each suite runs in its own subprocess, so no compiled executable,
    # cache or global JAX config of an earlier suite is live while it
    # runs: every suite's results stay bitwise-identical to a standalone
    # run, which the bitwise gates (trace_bitwise_match,
    # static_bitwise_match) depend on, and one suite's crash does not
    # end the others.  This parent never imports jax, so on a TPU host
    # each child in turn gets the chip.
    import subprocess

    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name in SUITE_NAMES:
        cmd = [sys.executable, "-m", "benchmarks.run", "--suite", name]
        if args.smoke:
            cmd.append("--smoke")
        if subprocess.run(cmd).returncode != 0:
            failures += 1
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
