"""The layer reduction (``bench/scopes.py``) on small traces whose numbers
are known by hand, and on one recorded on a TPU v5e chip."""
import json
from pathlib import Path

import pytest

import scopes as sc
import tracereduce as tr

MS = 1_000_000  # ns
DATA = Path(__file__).parent / "data"
STEP_OP = "jit(scan_run)/while/body/closed_call/repro.step"


def planes(devices=1):
    """A 20 ms window.  Host: the bench spans and JAX's runtime events on
    the window's thread, and an event on another thread.  Device: a
    loop, leaf ops in each phase and layer, and gaps."""
    host = {"python": [
        ("bench.window", 0.0, 20 * MS),
        ("bench.s6.steps", 0.0, 10 * MS),
        ("repro.run", 0.0, 2 * MS),
        ("PjitFunction(scan_run)", 0.5 * MS, 1 * MS),
        ("bench.s6.check", 10 * MS, 10 * MS),
        ("repro.eq11", 10 * MS, 1 * MS),
    ], "other thread": [("busy elsewhere", 0.0, 20 * MS)]}
    ops = [
        ("%while.1", 2 * MS, 6 * MS, "jit(scan_run)/while"),
        ("%fusion.1", 2 * MS, 1 * MS, f"{STEP_OP}/repro.mix/dot_general"),
        ("%fusion.2", 3 * MS, 2 * MS,
         f"{STEP_OP}/repro.local_grad/jvp(repro.hypergrad)/dot_general"),
        ("%fusion.3", 5 * MS, 1 * MS,
         f"{STEP_OP}/repro.local_grad/transpose(jvp())/mul"),
        ("%fusion.4", 6 * MS, 1 * MS, f"{STEP_OP}/add"),
        ("%copy.5", 7 * MS, 1 * MS, "jit(scan_run)/while/body"),
        ("%fusion.6", 11 * MS, 3 * MS,
         "jit(_convergence_metric)/repro.eq11/vmap(repro.hypergrad)/dot"),
        ("%fusion.7", 14 * MS, 1 * MS, "jit(init_state)/repro.init/mul"),
        ("%fusion.8", 19 * MS, 3 * MS, f"{STEP_OP}/repro.mix/dot"),
    ]
    out = {"/host:CPU": host}
    for i in range(devices):
        out[f"/device:TPU:{i}"] = {"XLA Ops": list(ops), "Steps": []}
    return out


def test_scope_path_matches_whole_components_bare_or_wrapped():
    assert sc.scope_path("jit(f)/vmap(transpose(jvp(repro.step)))/"
                         "repro.hypergrad/mul") == (sc.STEP, sc.HYPERGRAD)
    assert sc.scope_path("jit(f)/while/body/closed_call/dot") == ()
    assert sc.scope_path("jit(my.repro.step)/not_repro.mix/x") == ()


def test_leaf_ops_exclude_loops_and_calls():
    assert not sc.is_leaf("%while.37")
    assert not sc.is_leaf("%conditional.2")
    assert not sc.is_leaf("call")
    assert sc.is_leaf("%fusion.267")
    assert sc.is_leaf("%while_fusion.3")
    assert sc.is_leaf("%multiply_reduce_fusion.15")


def test_leaf_self_time_and_phases():
    red = sc.reduce_scopes(planes())
    assert red.window_s == pytest.approx(20e-3)
    # busy 2-8, 11-15, 19-20: 11 ms; leaves 1+2+1+1+1+3+1+1 = 11 ms
    assert red.busy_s == pytest.approx(11e-3)
    assert red.leaf_s == pytest.approx(11e-3)
    assert red.phases == pytest.approx({sc.STEP: 6e-3, sc.EQ11: 3e-3,
                                        sc.INIT: 1e-3, sc.NONE: 1e-3})
    assert sum(red.phases.values()) == pytest.approx(red.leaf_s)


def test_layers_inside_the_step():
    red = sc.reduce_scopes(planes())
    # the mix at 2-3 and 19-20; hypergrad innermost under local_grad;
    # a hypergradient inside repro.eq11 is not the step's
    assert red.layers == pytest.approx({
        sc.MIX: 2e-3, sc.HYPERGRAD: 2e-3, sc.LOCAL_GRAD: 1e-3,
        sc.STEP_OTHER: 1e-3})
    assert sum(red.layers.values()) == pytest.approx(red.phases[sc.STEP])
    b = red.breakdown()
    assert b["repro.step/repro.hypergrad"] == pytest.approx(2e-3)
    assert b[sc.NONE] == pytest.approx(1e-3)


def test_gaps_named_by_innermost_host_event():
    red = sc.reduce_scopes(planes())
    gaps = dict(red.idle_gaps_host)
    # 0-2: mid 1 ms in PjitFunction (0.5-1.5); 8-11: mid 9.5 in steps;
    # 15-19: mid 17 in bench.s6.check (repro.eq11 ended at 11)
    assert gaps == pytest.approx({"PjitFunction(scan_run)": 2e-3,
                                  "bench.s6.steps": 3e-3,
                                  "bench.s6.check": 4e-3})
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    assert "busy elsewhere" not in gaps


def test_per_device_average():
    one, two = sc.reduce_scopes(planes(1)), sc.reduce_scopes(planes(2))
    assert two.num_devices == 2
    assert two.phases == pytest.approx(one.phases)
    assert two.layers == pytest.approx(one.layers)
    assert dict(two.idle_gaps_host) == pytest.approx(
        dict(one.idle_gaps_host))


def test_layer_metrics_per_iteration_and_check():
    red = sc.reduce_scopes(planes())
    window = {"agent_steps": 10 * 50}           # 10 agents, 50 iterations
    params = {"num_agents": 10, "check_every": 25}
    got = sc.layer_metrics(red, window, params)
    assert got == pytest.approx({"hypergrad_ms.s6": 2.0 / 50,
                                 "local_grad_ms.s6": 1.0 / 50,
                                 "mix_ms.s6": 2.0 / 50,
                                 "eq11_ms.s6": 3.0 / 2})


def test_missing_window_or_device_is_an_error():
    p = planes()
    p["/host:CPU"]["python"] = p["/host:CPU"]["python"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        sc.reduce_scopes(p)
    with pytest.raises(ValueError, match="XLA Ops"):
        sc.reduce_scopes({"/host:CPU": planes()["/host:CPU"]})


def _varint(n):
    out = b""
    while True:
        byte, n = n & 0x7F, n >> 7
        out += bytes([byte | (0x80 if n else 0)])
        if not n:
            return out


def _field(number, value):
    """A protobuf field: an int as a varint, bytes/str length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, value):
    return _field(1, key) + _field(2, value)


def _stat_meta(key, name):
    return _field(5, _entry(key, _field(1, key) + _field(2, name)))


def _event_meta(key, name, *stats):
    body = _field(1, key) + _field(2, name) + b"".join(
        _field(5, st) for st in stats)
    return _field(4, _entry(key, body))


def test_metadata_stat_reads_tf_op_from_event_metadata(tmp_path):
    """An XSpace built by hand: the op_name as a string stat, as a
    reference to an interned stat name, and an op with none; a second
    plane without the stat; an event line that is skipped."""
    device = (
        _field(1, 1) + _field(2, "/device:TPU:0")
        + _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 7)))
        + _event_meta(7, "%fusion.1 = f32[2]",
                      _field(1, 2) + _field(3, 5),
                      _field(1, 1) + _field(5, "jit(f)/repro.step/add:"))
        + _event_meta(8, "%copy.2 = f32[2]", _field(1, 1) + _field(7, 9))
        + _event_meta(3, "%bitcast.3")
        + _stat_meta(1, "tf_op") + _stat_meta(2, "other")
        + _stat_meta(9, "jit(f)/repro.mix/dot:"))
    host = _field(2, "/host:CPU") + _event_meta(1, "bench.window")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert sc.metadata_stat(str(path)) == {
        "/device:TPU:0": {"%fusion.1 = f32[2]": "jit(f)/repro.step/add:",
                          "%copy.2 = f32[2]": "jit(f)/repro.mix/dot:"},
        "/host:CPU": {}}


def test_names_equal_the_programs():
    from repro import tracing
    assert (sc.STEP, sc.INIT, sc.EQ11) == tracing.PHASES == sc.PHASES
    assert (sc.MIX, sc.LOCAL_GRAD, sc.HYPERGRAD) == tracing.LAYERS \
        == sc.LAYERS


def test_tracereduce_unchanged_on_the_recorded_trace():
    """``tracereduce`` gives on ``small_trace_v5e.json`` exactly what it
    gave before the scope reduction existed."""
    red = tr.reduce_trace(json.loads(
        (DATA / "small_trace_v5e.json").read_text()))
    assert red == tr.Reduction(
        window_s=0.00240513, busy_s=3.96e-06, num_devices=1,
        top_ops=[["%fusion", 3.943e-06],
                 ["%copy-start", 1.4000000000000001e-08],
                 ["%copy-done", 3.0000000000000004e-09]],
        idle_gaps=[["bench.small.wait", 0.002010605],
                   ["bench.small.step", 0.000390565]],
        collective_s=0.0, collective_exposed_s=0.0, op_events=3)


def recorded():
    """``scoped_trace_v5e.json``: the Section-6 cell on one TPU v5e chip
    at its widths, with CG cut to 4 iterations and the eq.-11 descents to
    20 steps, traced over init, two 25-step blocks and one check under
    the bench spans.  Kept: the device's ``XLA Ops`` line (op name, start
    as a delta from the previous op, duration, index of its ``op_name``,
    in ns) and the host lines, as ``load_scoped_trace`` gives them."""
    doc = json.loads((DATA / "scoped_trace_v5e.json").read_text())
    out = {}
    for plane, lines in doc["planes"].items():
        out[plane] = {}
        for line, rows in lines.items():
            if plane.startswith(tr.DEVICE_PREFIX):
                evs, t = [], 0
                for name, dt, dur, op in rows:
                    t += dt
                    evs.append((doc["names"][name], float(t), float(dur),
                                doc["op_names"][op]))
            else:
                evs = [(n, float(s), float(d)) for n, s, d in rows]
            out[plane][line] = evs
    return out


def ns(seconds):
    return round(seconds * 1e9)


def test_recorded_v5e_leaf_self_time():
    planes = recorded()
    red = sc.reduce_scopes(planes)
    assert red.num_devices == 1
    assert ns(red.window_s) == 45455019
    assert red.busy_s == tr.reduce_trace(sc.plain(planes)).busy_s
    assert ns(red.busy_s) == 42867583
    # the loops' own events (%while.37, %while.38, ...) span their bodies
    # and are not summed; the leaves fall short of busy by the time
    # between a loop's body ops
    assert ns(red.leaf_s) == 42650643
    assert ns(sum(red.phases.values())) == ns(red.leaf_s)


def test_recorded_v5e_phases_and_layers():
    red = sc.reduce_scopes(recorded())
    assert {k: ns(v) for k, v in red.phases.items()} == {
        sc.STEP: 37252123, sc.INIT: 618781, sc.EQ11: 2209979,
        sc.NONE: 2569760}
    assert {k: ns(v) for k, v in red.layers.items()} == {
        sc.MIX: 337016, sc.LOCAL_GRAD: 31896698, sc.HYPERGRAD: 5016609,
        sc.STEP_OTHER: 1800}
    assert ns(sum(red.layers.values())) == ns(red.phases[sc.STEP])


def test_recorded_v5e_hypergradient_in_a_check_is_the_checks():
    ops = recorded()["/device:TPU:0"]["XLA Ops"]
    in_check = [d for n, _, d, op in ops if sc.is_leaf(n)
                and sc.HYPERGRAD in sc.scope_path(op)
                and sc.attribute(op) == (sc.EQ11, None)]
    in_step = [d for n, _, d, op in ops if sc.is_leaf(n)
               and sc.attribute(op) == (sc.STEP, sc.HYPERGRAD)]
    assert in_check and in_step
    assert sum(in_step) == 5016609          # all inside the window
    red = sc.reduce_scopes(recorded())
    assert ns(red.layers[sc.HYPERGRAD]) == sum(in_step)


def test_recorded_v5e_none_is_the_scan_programs_own_copies():
    """``(none)``: ops outside every phase, here the ``copy-done`` and
    ``copy`` ops XLA adds around the scan (``jit(scan_run)/while``)."""
    ops = recorded()["/device:TPU:0"]["XLA Ops"]
    none = {n.split(".")[0] for n, _, _, op in ops if sc.is_leaf(n)
            and sc.attribute(op)[0] == sc.NONE}
    assert none <= {"%copy-done", "%copy", "%copy-start", "%iota",
                    "%custom-call"}


def test_recorded_v5e_gaps_named_by_innermost_host_event():
    red = sc.reduce_scopes(recorded())
    assert [[k, ns(v)] for k, v in red.idle_gaps_host] == [
        ["$array.py:631 _value", 2577230],
        ["PjitFunction(init_state)", 10202],
        ["ParseArguments", 3], ["bench.s6.init", 1]]
    assert ns(sum(v for _, v in red.idle_gaps_host)) == ns(
        red.window_s - red.busy_s)
