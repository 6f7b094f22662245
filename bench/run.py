"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  ``BENCHMARK.json`` names the cell's
configuration, traffic and chips and lists the metrics; the traffic file
``bench/workloads/<traffic>.json`` names the driver
(``bench/drivers/<driver>.py``) and its parameters; the configuration is
``bench/configs/<config>.json``; each per-layer metric is read by
``bench/layer_metrics/<metric>.py``.

A run: set-up (JAX start, data and weights from ``--seed``, compiling or
loading every program the window uses, and for training cells the first
steps), then the measured window of ``--seconds`` seconds, then the peak
device memory, then the state is freed and the comparison with the plain
reference decides ``correct``.  ``--trace 1`` traces the window (as long
as the traffic's ``trace_seconds`` allows) and reports the per-layer
metrics instead of the end-to-end ones.

The last line of stdout is one JSON object; the numbers compared with
their limits end it (key ``compared``) and are also the last lines of
stderr.  Anything but a TPU with at least the cell's chips is refused
with a nonzero exit and no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's entry, traffic, configuration and metric lists."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf_entry = confs[cell["config"]]
    config = json.loads((REPO / conf_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "workloads" / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return dict(cell=cell, config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def require_devices(chips: int):
    """The cell's TPU devices; anything else ends the run."""
    import jax
    from peaks import peaks_for
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {dev.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found "
                         f"{len(devs)}")
    peaks_for(dev.device_kind)
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` or a fixed
    directory inside the checkout; every program is cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def traced_window(cell, seconds: float):
    """Run the window under the profiler; returns (window, reduction)."""
    import jax
    import tracereduce
    out = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    jax.profiler.start_trace(str(out))
    try:
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
            window = cell.window(seconds)
    finally:
        jax.profiler.stop_trace()
    try:
        red = tracereduce.reduce_trace(tracereduce.load_trace(str(out)))
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return window, red


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices=None, spec=None) -> dict:
    """One run of one cell; returns the result object.

    ``devices=None`` looks for the cell's TPU chips (the benchmark's
    path); a test passes the devices it wants, which skips that look,
    and may pass ``spec`` (``load_cell``'s, with the sizes cut to what a
    test can hold).  ``main`` turns the persistent compilation cache on
    first.
    """
    spec = spec or load_cell(workload)
    cell_entry, traffic = spec["cell"], spec["traffic"]
    chips = int(cell_entry["chips"])
    if devices is None:
        devices = require_devices(chips)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    cell = driver.setup(spec["config"], traffic["params"], seed, devices)
    setup_s = time.perf_counter() - T_START

    if trace:
        seconds = min(seconds, float(traffic["params"].get(
            "trace_seconds", seconds)))
        window, red = traced_window(cell, seconds)
    else:
        window, red = cell.window(seconds), None
    mem = memory_peak(devices)
    counters = cell.counters() if trace else {}
    cell.release()
    compared = cell.check()

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    metrics = {}
    result = {"correct": all(c["value"] <= c["limit"] for c in compared)
              and bool(compared),
              "attempted": window["attempted"], "failed": window["failed"]}
    if trace:
        from peaks import PEAKS
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = dict(window=window, trace=red, counters=counters,
                   chips=len(devices),
                   peaks=PEAKS.get(dev.device_kind), config=spec["config"],
                   params=traffic["params"])
        for m in spec["per_layer"]:
            reader = load_module(BENCH / "layer_metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_gaps}
    else:
        for m in spec["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] in window["metrics"]:
                metrics[m["name"]] = {"value": window["metrics"][m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]} for c in compared}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(REPO / "src"))
    enable_compile_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    ok = all(math.isfinite(m["value"]) for m in result["metrics"].values())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
