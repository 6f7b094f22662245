"""Names of the solver's layers in the JAX profiler's trace.

Device scopes (``scope``) are ``jax.named_scope``s: they reach the
compiled HLO as ``op_name`` metadata, so every op a profiler trace shows
on the device carries the layer it belongs to, and they change no
instruction.  Host spans (``host_span``) are
``jax.profiler.TraceAnnotation``s on the profiler's own clock; with no
trace running they cost a flag check.  Every name starts with
``repro.`` so that none collides with a path component of JAX's own
(``jit(step)``, ``while``, ``closed_call``).  docs/SOLVERS.md
("Tracing") lists what each covers.

``compile_events()`` counts JAX's jaxpr traces and backend compiles
(a persistent-cache load included) in this process; a steady loop does
not move it.
"""
from __future__ import annotations

import functools
import threading

import jax

# phases: the outermost repro scope of an op
STEP = "repro.step"          # one solver iteration (SolverBase.build)
INIT = "repro.init"          # an algorithm's init_state
EQ11 = "repro.eq11"          # the eq.-11 metric; also its host span
# layers inside a phase
MIX = "repro.mix"            # the consensus combine(s) of Steps 1 and 3
LOCAL_GRAD = "repro.local_grad"  # Step 2: the agents' local gradients
HYPERGRAD = "repro.hypergrad"    # the inverse-Hessian solve of eq. (5)
# LM backbone layers, inside local_grad (and hypergrad for lm_head)
MAMBA = "repro.mamba"        # Mamba mixer: in_proj, conv, x_proj, scan, gate
ATTN = "repro.attn"          # attention mixer: projections and softmax
FFN = "repro.ffn"            # gated feed-forward
LM_HEAD = "repro.lm_head"    # chunked CE and the head-space Neumann terms
# host spans
RUN = "repro.run"            # SolverBase.run's dispatch

PHASES = (STEP, INIT, EQ11)
LAYERS = (MIX, LOCAL_GRAD, HYPERGRAD)
LM_LAYERS = (MAMBA, ATTN, FFN, LM_HEAD)

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


def scope(name: str):
    """Device scope: the ops traced inside carry ``name`` in their path."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the function's ops under the device scope ``name``."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def host_span(name: str):
    """Host span on the profiler's clock, recorded only while it traces."""
    return jax.profiler.TraceAnnotation(name)


_compiles = 0
_listening = False
_lock = threading.Lock()


def _count_compiles(event: str, duration: float, **kwargs) -> None:
    global _compiles
    if event in _COMPILE_EVENTS:
        with _lock:
            _compiles += 1


def compile_events() -> int:
    """Jaxpr traces plus backend compiles since the first call.

    The ``jax.monitoring`` listener is registered on the first call, once
    per process: read it before and after a window and take the
    difference.
    """
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _count_compiles)
            _listening = True
        return _compiles
