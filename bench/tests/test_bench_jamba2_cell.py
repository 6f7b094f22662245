"""The jamba2-3b.s8k.ring4 cell on the CPU: a sound run is correct, and
every control and planted fault comes out as not correct.

The cell puts one agent on each of four devices, so the runs go to one
subprocess with four forced host devices.  It drives ``bench/run.py``'s
run without its look for a TPU, with a short window, at the cell's
14-layer period and its ratios but tiny widths (d_model 64, 2 held query
heads of 16, 32 held Mamba channels and FFN columns, 64 vocabulary rows,
sequences of 64 tokens, a pool of 3 pairs).  The sound run goes through
``run.run_cell``; each control and fault then runs the program's own
check steps against the sound run's reference (as ``bench/lm_control.py``
does on the chip), with the traffic file's limits.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
CELL = "jamba2-3b.s8k.ring4"
SEED = 2**31 + 4242
CUT_CONFIG = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=2, head_dim=16, vocab_size=64,
                  mamba_d_state=4, mamba_dt_rank=8, mamba_seq_chunk=16,
                  ce_chunk=32, held=dict(query_heads=2, kv_heads=1,
                                         mamba_channels=32, ffn_columns=32,
                                         vocab_rows=64))
CUT_TRAFFIC = dict(seq_len=64, pool=3)
CONTROLS = ("state_bf16", "matmul_f8", "no_mix", "no_attention")
FAULTS = ("stale_state", "half_batch", "altered_answer")

SCRIPT = """
import json, sys
import jax
import run
from drivers import lm_ring as driver
assert driver.CONTROLS == {controls!r} and driver.FAULTS == {faults!r}
spec = run.load_cell({cell!r})
spec["config"] = dict(spec["config"], **{config!r})
spec["traffic"] = dict(spec["traffic"], params=dict(
    spec["traffic"]["params"], **{traffic!r}))
conf, params = spec["config"], spec["traffic"]["params"]
devices = jax.devices()[:4]
out = {{"sound": run.run_cell({cell!r}, {seed}, 0.5, False,
                              devices=devices, spec=spec)}}
cell = driver.setup(conf, params, {seed}, devices)
cell.release()
cell.program_updates()
ref = cell.reference_updates()
for name in driver.CONTROLS + driver.FAULTS:
    with driver.planted(name):
        bad = driver.setup(conf, driver.control_params(params, name),
                           {seed}, devices)
        bad.release()
        compared = driver.compare(bad.program_updates(), ref,
                                  params["limits"])
    out[name] = dict(correct=all(c["value"] <= c["limit"]
                                 for c in compared),
                     compared={{c["name"]: c["value"] for c in compared}})
print(json.dumps(out, default=float))
"""


@pytest.fixture(scope="module")
def readings():
    code = SCRIPT.format(controls=CONTROLS, faults=FAULTS, cell=CELL,
                         config=CUT_CONFIG, traffic=CUT_TRAFFIC, seed=SEED)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{BENCH}:{BENCH.parent / 'src'}")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=BENCH,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(readings):
    res = readings["sound"]
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"agent_steps_per_s", "setup_s"}
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("control", CONTROLS)
def test_control_is_not_correct(readings, control):
    assert not readings[control]["correct"], readings[control]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(readings, fault):
    assert not readings[fault]["correct"], readings[fault]
