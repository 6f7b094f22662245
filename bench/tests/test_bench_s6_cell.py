"""The s6.m10.interact cell on the CPU: a sound run is correct, and the
control and every planted fault come out as not correct.

These drive ``bench/run.py``'s run without its look for a TPU, with a
short window, at the configuration's widths (784 inputs, 20 hidden
units, 10 classes, 10 agents) but a twentieth of its samples per agent, a
pool of two instances and 300 steps at most to a solve, so that a
test run holds them.
"""
import jax
import pytest

import faults
import run

CELL = "s6.m10.interact"
SEED = 2**31 + 12345
CUT_CONFIG = dict(n_per_agent=300)
CUT_TRAFFIC = dict(pool=2, sample_solves=1, max_steps=300)


def cpu():
    return jax.devices("cpu")[:1]


def cut_spec():
    spec = run.load_cell(CELL)
    spec["config"] = dict(spec["config"], **CUT_CONFIG)
    spec["traffic"] = dict(spec["traffic"], params=dict(
        spec["traffic"]["params"], **CUT_TRAFFIC))
    return spec


def test_sound_run_is_correct():
    res = run.run_cell(CELL, SEED, 0.5, False, devices=cpu(),
                       spec=cut_spec())
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"time_to_gap_s", "agent_steps_per_s",
                                   "setup_s"}
    assert list(res)[-1] == "compared"


def test_control_is_not_correct():
    spec = cut_spec()
    driver = run.load_module(run.BENCH / "drivers" / "s6_closed_loop.py",
                             "s6_driver_under_test")
    compared = driver.control(spec["config"], spec["traffic"]["params"],
                              SEED, cpu())
    assert any(c["value"] > c["limit"] for c in compared), compared


@pytest.mark.parametrize("fault", faults.names("s6_closed_loop"))
def test_planted_fault_is_not_correct(fault):
    with faults.planted("s6_closed_loop", fault):
        res = run.run_cell(CELL, SEED, 0.5, False, devices=cpu(),
                           spec=cut_spec())
    assert not res["correct"], (fault, res["compared"])
