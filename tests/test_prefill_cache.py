"""Fused prefill -> decode continuation consistency.

prefill() must populate the decode caches (KV ring buffers, mamba h +
conv tail, rwkv wkv/token-shift states) exactly as if the prompt had been
decoded token by token — across every mixer family.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M

ARCHS = ["llama3.2-3b", "mixtral-8x7b", "rwkv6-3b",
         "jamba-1.5-large-398b", "jamba2-3b", "gemma2-2b", "qwen3-14b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_matches_stepwise_decode(arch):
    cfg = get_config(arch).reduced(num_prefix_tokens=0, frontend="none")
    params = M.init_params(cfg, jax.random.PRNGKey(0), with_head=True)
    T, D = 12, 5
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T + D), 0,
                                cfg.vocab_size)

    cacheA = M.init_cache(cfg, batch=2, max_len=T + D)
    logA, cacheA = M.prefill(cfg, params, params["head"], tokens[:, :T],
                             cacheA)
    outsA = [logA]
    for t in range(T, T + D):
        lg, cacheA = M.decode_step(cfg, params, params["head"],
                                   tokens[:, t:t + 1], cacheA,
                                   jnp.asarray(t, jnp.int32))
        outsA.append(lg[:, 0])

    cacheB = M.init_cache(cfg, batch=2, max_len=T + D)
    outsB = []
    for t in range(T + D):
        lg, cacheB = M.decode_step(cfg, params, params["head"],
                                   tokens[:, t:t + 1], cacheB,
                                   jnp.asarray(t, jnp.int32))
        outsB.append(lg[:, 0])

    for a, b in zip(outsA, outsB[T - 1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_prefill_into_swa_ring_longer_than_window():
    """Prompt longer than the sliding window: the ring layout must place
    the last `window` keys so decode continues correctly."""
    cfg = get_config("mixtral-8x7b").reduced(sliding_window=8, num_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(2), with_head=True)
    T, D = 20, 4  # T > window
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, T + D), 0,
                                cfg.vocab_size)
    cacheA = M.init_cache(cfg, batch=1, max_len=8)
    _, cacheA = M.prefill(cfg, params, params["head"], tokens[:, :T], cacheA)
    outsA = []
    for t in range(T, T + D):
        lg, cacheA = M.decode_step(cfg, params, params["head"],
                                   tokens[:, t:t + 1], cacheA,
                                   jnp.asarray(t, jnp.int32))
        outsA.append(lg[:, 0])
    cacheB = M.init_cache(cfg, batch=1, max_len=8)
    outsB = []
    for t in range(T + D):
        lg, cacheB = M.decode_step(cfg, params, params["head"],
                                   tokens[:, t:t + 1], cacheB,
                                   jnp.asarray(t, jnp.int32))
        outsB.append(lg[:, 0])
    for a, b in zip(outsA, outsB[T:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)
