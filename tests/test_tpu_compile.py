"""The Pallas kernels compile for a TPU v5e at real widths.

Interpret mode accepts layouts and VMEM budgets that Mosaic refuses, so
each kernel is compiled ahead of time for a *described* v5e chip (the
TPU compiler is installed; no chip is attached) with ``interpret=False``.
A refusal — misaligned block, VMEM overflow, unsupported primitive —
raises here exactly as it would on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.consensus_step import ops as cs_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rwkv6 import ops as wkv_ops

# Section 6: m = 10 agents, the MLP backbone flattens to D = 760.
# m = 512 / 768: many-agent sweeps (768 overflowed 16 MiB of VMEM at the
# fixed 512-lane tile); m = 1536 runs at the narrowest (128-lane) tile.
CONSENSUS_SHAPES = [(10, 760), (512, 65536), (768, 8192), (1536, 8192)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text   # the Mosaic kernel, not a fallback


@pytest.mark.parametrize("m,d", CONSENSUS_SHAPES)
def test_consensus_step_compiles(one_chip, m, d):
    mat = _spec(one_chip, (m, m))
    x = _spec(one_chip, (m, d))
    _compile_has_kernel(
        lambda M, a, b, c, e: cs_ops.consensus_step(
            M, {"w": a}, {"w": b}, {"w": c}, {"w": e}, alpha=0.1,
            interpret=False),
        mat, x, x, x, x)


@pytest.mark.parametrize("m,d", CONSENSUS_SHAPES)
def test_consensus_mix_compiles(one_chip, m, d):
    _compile_has_kernel(
        lambda M, a: cs_ops.consensus_mix(M, {"w": a}, interpret=False),
        _spec(one_chip, (m, m)), _spec(one_chip, (m, d)))


def test_flash_attention_compiles_at_smollm_360m_widths(one_chip):
    from repro.configs import get_config
    cfg = get_config("smollm-360m")
    b, s = 4, 2048
    q = _spec(one_chip, (b, s, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    kv = _spec(one_chip, (b, s, cfg.num_kv_heads, cfg.head_dim),
               jnp.bfloat16)
    _compile_has_kernel(
        lambda q_, k_, v_: fa_ops.flash_attention(q_, k_, v_,
                                                  interpret=False),
        q, kv, kv)


def test_wkv6_compiles_at_rwkv6_3b_widths(one_chip):
    from repro.configs import get_config
    cfg = get_config("rwkv6-3b")
    h, n = cfg.num_heads, cfg.d_model // cfg.num_heads
    b, s = 4, 2048
    t = _spec(one_chip, (b, s, h, n), jnp.bfloat16)
    _compile_has_kernel(
        lambda r, k, v, w, u: wkv_ops.wkv6(r, k, v, w, u, interpret=False),
        t, t, t, t, _spec(one_chip, (h, n)))


def test_consensus_tile_narrows_with_m_and_refuses_too_many_agents():
    from repro.kernels.consensus_step.kernel import pick_block_d
    assert pick_block_d(768, 8192, 4, streams=6) == 512
    assert pick_block_d(1536, 8192, 4, streams=6) == 128
    assert pick_block_d(10, 300, 4, streams=6) == 300   # one full block
    with pytest.raises(ValueError, match="m=2048"):
        pick_block_d(2048, 8192, 4, streams=6)
