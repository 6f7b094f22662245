"""Reduce a profiler trace to device time per layer of the program.

The program names its layers (``src/repro/tracing.py``): device scopes
that reach every compiled op's ``op_name`` metadata, and host spans.
The names are written here literally, so that this file reads no code
of the program under test; ``bench/tests/test_bench_scopes.py`` checks
that they still equal the program's.

Definitions (over the traced window, the host span ``bench.window``):

* an op's scope path: the ``repro.*`` components of its ``op_name``, in
  order; a component matches whole, bare or wrapped by a transform
  (``jvp(repro.hypergrad)``, ``vmap(transpose(jvp(repro.step)))``);
* self time: the summed durations of the leaf ops of the device's
  ``XLA Ops`` line, clipped to the window; ``while``, ``conditional``
  and ``call`` ops are not leaves, their events span their bodies;
* phase: an op's outermost ``repro.step``, ``repro.init`` or
  ``repro.eq11`` component, else ``(none)``;
* layer (inside ``repro.step`` only): the innermost ``repro.mix``,
  ``repro.local_grad`` or ``repro.hypergrad`` component, else
  ``(step other)``; a hypergradient inside an eq.-11 check counts as
  ``repro.eq11``;
* host-named idle gaps: the gaps of ``tracereduce`` (device busy is the
  union of all its ``XLA Ops`` events), each named by the innermost host
  event of any name (``repro.*``, ``bench.*`` or JAX's runtime) on the
  host thread that holds ``bench.window`` and covers the gap's midpoint.

``load_scoped_trace`` reads an ``.xplane.pb`` into the plain
``{plane: {line: [event, ...]}}`` data ``reduce_scopes`` works on: host
events ``(name, start_ns, duration_ns)``, device ops
``(name, start_ns, duration_ns, op_name)``, the ``op_name`` taken from
the ``tf_op`` stat of the op's event metadata.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs a cell's window untraced, traced, then untraced twice, in one
process on the chip, and prints one JSON line: the end-to-end numbers of
each window, the layer reduction of the traced one, and the compile
events inside each window (``repro.tracing.compile_events``).
"""
from __future__ import annotations

import dataclasses
import os
import re

import tracereduce as tr

STEP, INIT, EQ11 = "repro.step", "repro.init", "repro.eq11"
MIX, LOCAL_GRAD, HYPERGRAD = "repro.mix", "repro.local_grad", "repro.hypergrad"
PHASES = (STEP, INIT, EQ11)
LAYERS = (MIX, LOCAL_GRAD, HYPERGRAD)
NONE, STEP_OTHER = "(none)", "(step other)"
NO_HOST_EVENT = "(no host event)"
LOOPS = frozenset({"while", "conditional", "call"})
OP_NAME_STAT = "tf_op"

_COMPONENT = re.compile(r"^(?:[A-Za-z_]\w*\()*(repro\.\w+)\)*$")
_SUFFIX = re.compile(r"\.\d+$")


def scope_path(op_name: str) -> tuple:
    """The ``repro.*`` components of an op's ``op_name``, outer first."""
    out = []
    for comp in op_name.split("/"):
        m = _COMPONENT.match(comp)
        if m:
            out.append(m.group(1))
    return tuple(out)


def attribute(op_name: str) -> tuple:
    """``(phase, layer)``; ``layer`` is None outside ``repro.step``."""
    path = scope_path(op_name)
    phase = next((c for c in path if c in PHASES), NONE)
    if phase != STEP:
        return phase, None
    inner = path[path.index(STEP) + 1:]
    layer = next((c for c in reversed(inner) if c in LAYERS), STEP_OTHER)
    return phase, layer


def is_leaf(name: str) -> bool:
    """False for ops whose events span their bodies (``%while.3``)."""
    return _SUFFIX.sub("", name.lstrip("%")) not in LOOPS


@dataclasses.dataclass
class ScopeReduction:
    window_s: float
    busy_s: float            # per device, as tracereduce computes it
    leaf_s: float            # per device: leaf ops' summed self time
    phases: dict             # {phase or "(none)": seconds per device}
    layers: dict             # {layer or "(step other)": s in repro.step}
    idle_gaps_host: list     # [[host event, seconds], ...] longest first
    num_devices: int

    def breakdown(self) -> dict:
        """``{phase: s, "repro.step/<layer>": s}``, seconds per device."""
        out = dict(self.phases)
        out.update({f"{STEP}/{k}": v for k, v in self.layers.items()})
        return out


def window_line(planes: dict):
    """``(lo, hi, events)`` of the host line that holds ``bench.window``."""
    for pname, lines in planes.items():
        if pname.startswith(tr.DEVICE_PREFIX):
            continue
        for evs in lines.values():
            wins = [(s, s + d) for n, s, d, *_ in evs if n == tr.WINDOW_SPAN]
            if wins:
                return (min(s for s, _ in wins), max(e for _, e in wins),
                        [(n, s, s + d) for n, s, d, *_ in evs])
    raise ValueError(f"trace has no {tr.WINDOW_SPAN!r} host span")


def name_gaps(gaps, host_events) -> dict:
    """Seconds (ns here) of ``gaps`` per innermost covering host event.

    One sweep over the thread's events in start order with a stack of
    the open ones: events on one thread nest.
    """
    evs = sorted(host_events, key=lambda ev: (ev[1], -ev[2]))
    out: dict = {}
    stack, i = [], 0
    for s, e in sorted(gaps):
        mid = 0.5 * (s + e)
        while i < len(evs) and evs[i][1] <= mid:
            while stack and stack[-1][2] <= evs[i][1]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][2] <= mid:
            stack.pop()
        name = stack[-1][0] if stack else NO_HOST_EVENT
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def reduce_scopes(planes: dict, top: int = 10) -> ScopeReduction:
    lo, hi, host = window_line(planes)
    devices = {p: l[tr.OPS_LINE] for p, l in planes.items()
               if p.startswith(tr.DEVICE_PREFIX) and tr.OPS_LINE in l}
    if not devices:
        raise ValueError(f"trace has no {tr.DEVICE_PREFIX}* plane with an "
                         f"{tr.OPS_LINE!r} line")
    busy = leaf = 0.0
    phases = {p: 0.0 for p in PHASES + (NONE,)}
    layers = {k: 0.0 for k in LAYERS + (STEP_OTHER,)}
    gap_time: dict = {}
    cache: dict = {}
    for evs in devices.values():
        inside = [(n, s, s + d, path) for n, s, d, path in evs
                  if s + d > lo and s < hi]
        merged = tr.union(tr.clip([(s, e) for _, s, e, _ in inside], lo, hi))
        busy += tr.measure(merged)
        for n, s, e, path in inside:
            if not is_leaf(n):
                continue
            dur = min(e, hi) - max(s, lo)
            leaf += dur
            if path not in cache:
                cache[path] = attribute(path)
            phase, layer = cache[path]
            phases[phase] += dur
            if layer is not None:
                layers[layer] += dur
        for k, v in name_gaps(tr.subtract([[lo, hi]], merged),
                              host).items():
            gap_time[k] = gap_time.get(k, 0.0) + v
    nd, ns = len(devices), 1e-9
    gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]
    return ScopeReduction(
        window_s=(hi - lo) * ns, busy_s=busy * ns / nd,
        leaf_s=leaf * ns / nd,
        phases={k: v * ns / nd for k, v in phases.items()},
        layers={k: v * ns / nd for k, v in layers.items()},
        idle_gaps_host=[[k, v * ns / nd] for k, v in gaps],
        num_devices=nd)


def layer_metrics(red: ScopeReduction, window: dict, params: dict) -> dict:
    """Per-iteration and per-check milliseconds of the Section-6 cell."""
    iters = window["agent_steps"] / int(params["num_agents"])
    if not iters:
        return {}
    checks = iters / int(params["check_every"])
    return {"hypergrad_ms.s6": 1e3 * red.layers[HYPERGRAD] / iters,
            "local_grad_ms.s6": 1e3 * red.layers[LOCAL_GRAD] / iters,
            "mix_ms.s6": 1e3 * red.layers[MIX] / iters,
            "eq11_ms.s6": 1e3 * red.phases[EQ11] / checks}


# -- reading an .xplane.pb ---------------------------------------------------
#
# ``jax.profiler.ProfileData`` gives each event's own stats; on a TPU the
# ``op_name`` (stat ``tf_op``) sits on the event's metadata, which it does
# not expose.  The few lines below read the metadata of each plane from
# the protobuf wire format (tsl/profiler/protobuf/xplane.proto: XSpace
# planes = 1; XPlane name = 2, event_metadata = 4, stat_metadata = 5;
# XEventMetadata name = 2, stats = 5; XStatMetadata name = 2; XStat
# metadata_id = 1, str_value = 5, ref_value = 7) and skip the events.


def _varint(buf, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field, value)`` of one message; a length-delimited value is its
    ``(start, end)`` in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """``(key, value)`` of a protobuf map entry."""
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def metadata_stat(path: str, stat: str = OP_NAME_STAT) -> dict:
    """``{plane: {event metadata name: value of stat}}`` of an xplane."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, pv)
            elif pf == 4:
                events.append(_map_value(buf, pv)[1])
            elif pf == 5:
                key, meta = _map_value(buf, pv)
                stat_names[key] = next((_text(buf, v) for sf, v in
                                        _fields(buf, *meta) if sf == 2), "")
        ids = {k for k, v in stat_names.items() if v == stat}
        found = {}
        for meta in events:
            ev_name = value = None
            for mf, mv in _fields(buf, *meta):
                if mf == 2:
                    ev_name = _text(buf, mv)
                elif mf == 5:
                    xs = dict(_fields(buf, *mv))
                    if xs.get(1) in ids:
                        value = (_text(buf, xs[5]) if 5 in xs
                                 else stat_names.get(xs.get(7)))
            if ev_name is not None and value is not None:
                found[ev_name] = value
        out[name] = found
    return out


def load_scoped_trace(path: str) -> dict:
    """The trace with each device op's ``op_name`` (its ``tf_op`` stat,
    less the ``:<type>`` suffix; ``""`` where the op has none)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    op_names = metadata_stat(path)
    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        names = op_names.get(plane.name, {})
        device = plane.name.startswith(tr.DEVICE_PREFIX)
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            if device and line.name == tr.OPS_LINE:
                evs.extend((tr.short_name(e.name), float(e.start_ns),
                            float(e.duration_ns),
                            names.get(e.name, "").rsplit(":", 1)[0])
                           for e in line.events)
            else:
                evs.extend((tr.short_name(e.name), float(e.start_ns),
                            float(e.duration_ns)) for e in line.events)
    return planes


def plain(planes: dict) -> dict:
    """The trace as ``tracereduce`` reads it: no ``op_name`` on ops."""
    return {p: {ln: [ev[:3] for ev in evs] for ln, evs in lines.items()}
            for p, lines in planes.items()}


# -- one process: untraced and traced windows of a cell ----------------------

def measure(workload: str, seed: int, seconds: float, trace_dir: str,
            devices=None, spec=None) -> dict:
    """Windows of one cell in one process: untraced, traced (the profiler
    writes to ``trace_dir``), untraced twice.  Each is as long as the
    traffic's ``trace_seconds`` allows.  ``devices`` and ``spec`` as in
    ``run.run_cell``."""
    import jax
    import run
    from repro import tracing
    spec = spec or run.load_cell(workload)
    params = spec["traffic"]["params"]
    if devices is None:
        devices = run.require_devices(int(spec["cell"]["chips"]))
    driver = run.load_module(
        run.BENCH / "drivers" / f"{spec['traffic']['driver']}.py",
        f"bench_driver_{spec['traffic']['driver']}")
    cell = driver.setup(spec["config"], params, seed, devices)
    seconds = min(seconds, float(params.get("trace_seconds", seconds)))
    windows = []
    for traced in (False, True, False, False):
        before = tracing.compile_events()
        if traced:
            jax.profiler.start_trace(trace_dir)
            try:
                with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                    w = cell.window(seconds)
            finally:
                jax.profiler.stop_trace()
        else:
            w = cell.window(seconds)
        windows.append(dict(w, traced=traced,
                            compile_events=tracing.compile_events() - before))
    cell.release()
    planes = load_scoped_trace(trace_dir)
    red = reduce_scopes(planes)
    base = tr.reduce_trace(plain(planes))
    traced = windows[1]
    return {
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "windows": [{k: w[k] for k in ("traced", "metrics", "attempted",
                                       "failed", "elapsed_s", "agent_steps",
                                       "compile_events")} for w in windows],
        "ops_with_op_name": sum(
            bool(ev[3]) for p, lines in planes.items()
            if p.startswith(tr.DEVICE_PREFIX)
            for ev in lines.get(tr.OPS_LINE, ())),
        "busy_s": base.busy_s, "window_s": base.window_s,
        "leaf_s": red.leaf_s,
        "layers": red.breakdown(),
        "idle_gaps": base.idle_gaps,
        "idle_gaps_host": red.idle_gaps_host,
        "metrics": dict(layer_metrics(red, traced, params),
                        **{"compiles_in_window.s6":
                           traced["compile_events"]}),
    }


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    import tempfile
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    import run
    sys.path.insert(0, str(run.REPO / "src"))
    run.enable_compile_cache()
    # JAX's cache key drops op metadata by default: a program cached
    # before the program had its scopes would be served without them
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    with tempfile.TemporaryDirectory(prefix="bench_scopes_") as tmp:
        out = measure(args.workload, args.seed, args.seconds, tmp)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
