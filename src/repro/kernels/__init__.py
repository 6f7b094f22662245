"""Pallas kernels and the one rule for how they run.

Every kernel entry point takes ``interpret=None`` and resolves it here
from the backend JAX runs on: the interpreter on CPU (validation), the
compiled Mosaic kernel on TPU.  Any other backend has no kernel path and
is an error, so a kernel never falls back to the interpreter on a chip.
"""
import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else what ``jax.default_backend()`` needs."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"backend {backend!r} has neither")
