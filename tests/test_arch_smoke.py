"""Per-architecture smoke tests (assignment requirement).

For each assigned architecture: instantiate a REDUCED variant of the same
family (2 layers / 1 period, d_model <= 512, <= 4 experts) and run one
forward + one train step on CPU asserting output shapes and no NaNs, plus
one decode step against a KV/state cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import model as M

BATCH, SEQ = 2, 16


def _inputs(small, key):
    tokens = jax.random.randint(key, (BATCH, SEQ), 0, small.vocab_size)
    prefix = None
    if small.frontend != "none" and small.num_prefix_tokens:
        fd = small.frontend_dim or small.d_model
        prefix = 0.1 * jax.random.normal(key, (BATCH, small.num_prefix_tokens, fd))
    return tokens, prefix


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    cfg.validate()
    # spot-check the assigned dimensions are encoded exactly
    expect = {
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "jamba2-3b": (28, 2560, 20, 1, 8192, 65536),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expect


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_limits(arch):
    small = get_config(arch).reduced()
    assert small.d_model <= 512
    assert small.num_experts <= 4
    assert small.num_layers <= 8  # <= 1 period for hybrids, 2 layers else
    small.validate()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    small = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_params(small, key, with_head=True)
    tokens, prefix = _inputs(small, key)
    logits, aux = M.forward(small, params, tokens, prefix_embed=prefix)
    total = SEQ + (small.num_prefix_tokens if prefix is not None else 0)
    assert logits.shape == (BATCH, total, small.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    """One SGD step on the LM loss: grads finite, params move."""
    small = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    params = M.init_params(small, key, with_head=True)
    tokens, prefix = _inputs(small, key)

    def loss_fn(p):
        logits, aux = M.forward(small, p, tokens, prefix_embed=prefix)
        return M.lm_loss(small, logits, tokens, aux)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
    new_params = jax.tree_util.tree_map(lambda p, g: p - 1e-2 * g,
                                        params, grads)
    loss2 = loss_fn(new_params)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_matches_forward(arch):
    small = get_config(arch).reduced(num_prefix_tokens=0, frontend="none")
    key = jax.random.PRNGKey(2)
    params = M.init_params(small, key, with_head=True)
    T = 8
    tokens = jax.random.randint(key, (BATCH, T), 0, small.vocab_size)
    full_logits, _ = M.forward(small, params, tokens, remat=False,
                               moe_impl="exact")
    cache = M.init_cache(small, batch=BATCH, max_len=32)
    outs = []
    for t in range(T):
        logits, cache = M.decode_step(small, params, params["head"],
                                      tokens[:, t:t + 1], cache,
                                      jnp.asarray(t, jnp.int32))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               atol=2e-4, rtol=1e-3)


def test_sliding_window_cache_is_bounded():
    """SWA archs allocate only `window` cache slots (long_500k feasibility)."""
    small = get_config("mixtral-8x7b").reduced()
    assert small.sliding_window == 64
    cache = M.init_cache(small, batch=1, max_len=4096)
    k = cache[0]["attn"]["k"]  # (periods, batch, slots, kv, hd)
    assert k.shape[2] == 64


def test_ring_buffer_decode_beyond_window():
    """Decode past the window: ring buffer wraps, output stays correct."""
    small = get_config("mixtral-8x7b").reduced(sliding_window=8,
                                               num_layers=2)
    key = jax.random.PRNGKey(3)
    params = M.init_params(small, key, with_head=True)
    T = 20  # > window
    tokens = jax.random.randint(key, (1, T), 0, small.vocab_size)
    full_logits, _ = M.forward(small, params, tokens, remat=False,
                               moe_impl="exact")
    cache = M.init_cache(small, batch=1, max_len=8)
    for t in range(T):
        logits, cache = M.decode_step(small, params, params["head"],
                                      tokens[:, t:t + 1], cache,
                                      jnp.asarray(t, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               atol=2e-4, rtol=1e-3)


def test_gemma2_long_context_window_mode():
    """long_context_mode='window' bounds every layer's cache (DESIGN.md §4)."""
    import dataclasses
    small = get_config("gemma2-2b").reduced()
    windowed = dataclasses.replace(small, long_context_mode="window")
    cache = M.init_cache(windowed, batch=1, max_len=100_000)
    # both period positions (local AND the formerly-global layer) bounded
    for pos in range(2):
        k = cache[pos]["attn"]["k"]
        assert k.shape[2] <= windowed.local_window

    native = M.init_cache(small, batch=1, max_len=1000)
    assert native[0]["attn"]["k"].shape[2] <= small.local_window  # local
    assert native[1]["attn"]["k"].shape[2] == 1000                # global
