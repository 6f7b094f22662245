"""The trace reduction on small traces whose numbers are known by hand."""
import pytest

import tracereduce as tr

MS = 1_000_000  # ns


def planes(devices=1):
    """A 10 ms window: host spans, device ops with known gaps."""
    host = {"python": [
        ("bench.window", 0.0, 10 * MS),
        ("bench.s6.steps", 0.0, 6 * MS),
        ("bench.s6.check", 6 * MS, 4 * MS),
        ("other", 1 * MS, 1 * MS),
    ]}
    ops = [
        ("fusion.1", 1 * MS, 2 * MS),            # 1-3
        ("fusion.2", 2 * MS, 2 * MS),            # 2-4, overlaps
        ("collective-permute-done.3", 5 * MS, 2 * MS),  # 5-7, exposed 5-6
        ("fusion.1", 6 * MS, 1 * MS),            # 6-7
        ("fusion.4", 9 * MS, 3 * MS),            # 9-12, clipped to 9-10
    ]
    out = {"/host:CPU": host}
    for i in range(devices):
        out[f"/device:TPU:{i}"] = {"XLA Ops": list(ops), "Steps": []}
    return out


def test_busy_idle_and_ops():
    red = tr.reduce_trace(planes())
    assert red.window_s == pytest.approx(10e-3)
    # busy: 1-4, 5-7, 9-10 -> 6 ms
    assert red.busy_s == pytest.approx(6e-3)
    assert red.idle_share == pytest.approx(0.4)
    ops = dict(red.top_ops)
    assert ops["fusion.1"] == pytest.approx(3e-3)
    assert ops["fusion.4"] == pytest.approx(1e-3)
    assert red.top_ops[0][0] == "fusion.1"


def test_gaps_named_by_innermost_span():
    red = tr.reduce_trace(planes())
    gaps = dict(red.idle_gaps)
    # gaps 0-1 and 4-5 fall in bench.s6.steps, 7-9 in bench.s6.check
    assert gaps["bench.s6.steps"] == pytest.approx(2e-3)
    assert gaps["bench.s6.check"] == pytest.approx(2e-3)
    assert "other" not in gaps


def test_exposed_collectives():
    red = tr.reduce_trace(planes())
    assert red.collective_s == pytest.approx(2e-3)
    assert red.collective_exposed_s == pytest.approx(1e-3)


def test_per_device_average():
    one, two = tr.reduce_trace(planes(1)), tr.reduce_trace(planes(2))
    assert two.num_devices == 2
    assert two.busy_s == pytest.approx(one.busy_s)
    assert dict(two.top_ops) == pytest.approx(dict(one.top_ops))


def test_interval_helpers():
    assert tr.union([(3, 4), (1, 2), (1.5, 3.5)]) == [[1, 4]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5],
                                                         [7, 10]]
    assert tr.measure([[0, 1], [2, 4]]) == 3


def test_missing_window_or_device_is_an_error():
    p = planes()
    p["/host:CPU"]["python"] = p["/host:CPU"]["python"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_trace(p)
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.reduce_trace({"/host:CPU": planes()["/host:CPU"]})


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e chip: three dispatches of a jitted
    512 x 512 product chain under ``bench.window`` / ``bench.small.*``
    spans, reduced to the device's ``XLA Ops`` line and the host's bench
    spans (``bench/tests/data/small_trace_v5e.json``).  Its device events
    sit about 1.2 ms earlier than the host spans that issued them, so
    only the last dispatch falls inside the host window."""
    import json
    from pathlib import Path
    path = Path(__file__).parent / "data" / "small_trace_v5e.json"
    red = tr.reduce_trace(json.loads(path.read_text()))
    assert red.num_devices == 1
    assert red.window_s == pytest.approx(2405130e-9)
    assert red.busy_s == pytest.approx((14 + 3 + 3943) * 1e-9)
    assert sum(s for _, s in red.idle_gaps) == pytest.approx(
        red.window_s - red.busy_s)
    assert red.top_ops[0][0] == "%fusion"
    assert {n for n, _ in red.idle_gaps} <= {"bench.small.step",
                                             "bench.small.wait"}
    assert red.collective_s == 0.0
