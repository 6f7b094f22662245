"""Shared pytest fixtures.

``jax.clear_caches()`` runs after every test module.  A test worker runs
many modules in one process, and every jitted program each of them
compiles stays alive in JAX's caches until the process exits; dropping
the caches at module boundaries bounds the worker's live executables and
host memory at the footprint of one module, at the cost of cross-module
recompiles.
"""
import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    jax.clear_caches()
