"""Readings of a cell's control and planted faults, with the harness's
verdict on each.

    python3 bench/control.py --workload <name> --seeds 1 2 3
    python3 bench/control.py --workload <name> --seeds 1 2 3 --program matmul_high
    python3 bench/control.py --workload <name> --seeds 1 2 3 --fault half_batch

Without ``--program`` or ``--fault``: the plain reference at the
precision below the configuration's, in the program's place.  With
``--program``: a whole run of the cell with one of the program's own
lower-precision paths switched on (``bench/faults.py``, ``CONTROLS``).
With ``--fault``: a whole run with that fault planted.  Prints one JSON
line per seed with each compared number, its limit and ``correct`` as
``bench/run.py`` decides it.  A control or a fault has to come out as
not correct; the control's smallest reading over the seeds is the upper
reading a limit is set under (PERF.md).  Runs on the cell's chips, like
``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def verdict(compared: list) -> bool:
    return bool(compared) and all(c["value"] <= c["limit"] for c in compared)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    group = ap.add_mutually_exclusive_group()
    group.add_argument("--program", default=None,
                       help="a whole run with this lower-precision path of "
                            "the program switched on (faults.CONTROLS)")
    group.add_argument("--fault", default=None,
                       help="a whole run with this fault of bench/faults.py "
                            "planted")
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of a --program or --fault run")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.REPO / "src"))
    spec = run.load_cell(args.workload)
    run.enable_compile_cache()
    devices = run.require_devices(int(spec["cell"]["chips"]))
    traffic = spec["traffic"]
    driver = run.load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                             f"bench_driver_{traffic['driver']}")
    planted = args.program or args.fault
    for seed in args.seeds:
        if planted:
            import faults
            with faults.planted(traffic["driver"], planted):
                res = run.run_cell(args.workload, seed, args.seconds, False,
                                   devices=devices)
            compared = [dict(name=k, **v) for k, v in res["compared"].items()]
        else:
            compared = driver.control(spec["config"], traffic["params"],
                                      seed, devices)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control": planted or "reference",
            "correct": verdict(compared),
            "compared": {c["name"]: {"value": c["value"],
                                     "limit": c["limit"]}
                         for c in compared}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
