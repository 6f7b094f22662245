"""Reduce a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``load_trace`` reads it with ``jax.profiler.ProfileData`` into plain
``{plane: {line: [(name, start_ns, duration_ns), ...]}}`` data, and
``reduce_trace`` works on that data alone, so a test can feed it a small
recorded trace.

Definitions (all over the traced window, the host span ``bench.window``):

* busy: the union of the intervals in which an operation of the device's
  ``XLA Ops`` line runs, averaged over the devices in the trace;
* idle gaps: the complement of busy inside the window, each gap named by
  the innermost ``bench.*`` host span that covers its midpoint;
* top ops: device time summed per operation name, averaged over devices
  (a loop's own event spans its body, whose ops are counted again);
* collectives: operations whose name says all-reduce, all-gather,
  reduce-scatter, collective-permute, all-to-all, send or recv; their
  exposed time is the part of their union during which no other
  operation runs on that device.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|\bsend\b|\brecv\b|send-done|recv-done", re.IGNORECASE)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    num_devices: int
    top_ops: list            # [[name, seconds], ...] longest first
    idle_gaps: list          # [[host span, seconds], ...] longest first
    collective_s: float      # per device
    collective_exposed_s: float  # per device
    op_events: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def short_name(name: str) -> str:
    """An HLO op event carries its whole instruction text; keep its name
    (``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``)."""
    return name.split(" = ", 1)[0]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_trace(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, duration_ns)]}}`` of an xplane."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend((short_name(e.name), float(e.start_ns),
                        float(e.duration_ns)) for e in line.events)
    return planes


def union(intervals):
    """Merge ``[(start, end)]`` into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Disjoint sorted intervals ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def host_spans(planes: dict):
    """``[(name, start, end)]`` of every ``bench.*`` span on any host line."""
    spans = []
    for pname, lines in planes.items():
        if pname.startswith(DEVICE_PREFIX):
            continue
        for evs in lines.values():
            spans.extend((n, s, s + d) for n, s, d in evs
                         if n.startswith(SPAN_PREFIX))
    return spans


def reduce_trace(planes: dict, top: int = 10) -> Reduction:
    spans = host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]

    devices = {p: l[OPS_LINE] for p, l in planes.items()
               if p.startswith(DEVICE_PREFIX) and OPS_LINE in l}
    if not devices:
        raise ValueError(f"trace has no {DEVICE_PREFIX}* plane with an "
                         f"{OPS_LINE!r} line")
    busy = coll = exposed = 0.0
    op_time: dict = {}
    gap_time: dict = {}
    n_events = 0
    for evs in devices.values():
        evs = [(n, s, s + d) for n, s, d in evs if s + d > lo and s < hi]
        n_events += len(evs)
        merged = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy += measure(merged)
        for n, s, e in evs:
            op_time[n] = op_time.get(n, 0.0) + (min(e, hi) - max(s, lo))
        c = union(clip([(s, e) for n, s, e in evs if COLLECTIVE.search(n)],
                       lo, hi))
        other = union(clip([(s, e) for n, s, e in evs
                            if not COLLECTIVE.search(n)], lo, hi))
        coll += measure(c)
        exposed += measure(subtract(c, other))
        for s, e in subtract([[lo, hi]], merged):
            mid = 0.5 * (s + e)
            cover = [sp for sp in inner if sp[1] <= mid < sp[2]]
            name = (min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover
                    else "(no bench span)")
            gap_time[name] = gap_time.get(name, 0.0) + (e - s)
    nd = len(devices)
    ns = 1e-9

    def ranked(d):
        return [[k, v * ns / nd] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return Reduction(window_s=(hi - lo) * ns, busy_s=busy * ns / nd,
                     num_devices=nd, top_ops=ranked(op_time),
                     idle_gaps=ranked(gap_time),
                     collective_s=coll * ns / nd,
                     collective_exposed_s=exposed * ns / nd,
                     op_events=n_events)
