"""Production meshes.

Single pod:  (16, 16)      axes ("data", "model")    — 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

The *agent* axis of the paper (the peer-to-peer network) is the data axis,
extended across pods in the multi-pod mesh: agents = pod-major ring, so
only the two ring edges crossing the pod boundary use DCI (DESIGN.md §3).

``shape=`` overrides the hard-coded pod shapes for anything smaller —
the localhost multi-process driver (scripts/launch_local.py) and the CI
smoke runs build e.g. a ``(8,)`` mesh over 2 processes x 4 forced host
devices.  Axis names default by rank: ``("data",)``, ``("data",
"model")``, ``("pod", "data", "model")``.

Every mesh the program builds goes through ``make_mesh``, which marks
all axes ``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes, under
which an einsum whose contracting dim is sharded over ``model`` is a type
error instead of an all-reduce the partitioner inserts.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "agent_axes", "agent_count",
           "model_axis"]

_DEFAULT_AXES = {1: ("data",), 2: ("data", "model"),
                 3: ("pod", "data", "model")}


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (partitioner-driven)."""
    shape = tuple(shape)
    return jax.make_mesh(shape, tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False,
                         shape: Sequence[int] | None = None,
                         axis_names: Sequence[str] | None = None):
    """Build the device mesh, hard-failing on a device shortfall.

    Without ``shape`` this is the fixed 256-chip pod (512 with
    ``multi_pod``).  ``shape`` overrides it with any validated shape
    (rank 1-3, positive dims); ``axis_names`` must match its rank and
    defaults to the rank's conventional names.  In a multi-process run
    ``jax.devices()`` spans every process, so the same call on every
    process yields the same global mesh.
    """
    if shape is None:
        if axis_names is not None:
            raise ValueError("axis_names= requires an explicit shape=")
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    else:
        if multi_pod:
            raise ValueError("pass either multi_pod=True or shape=, not both")
        shape = tuple(int(s) for s in shape)
        if not shape or any(s < 1 for s in shape):
            raise ValueError(f"mesh shape must be positive dims, got {shape}")
        if axis_names is None:
            axes = _DEFAULT_AXES.get(len(shape))
            if axes is None:
                raise ValueError(
                    f"no default axis names for a rank-{len(shape)} mesh; "
                    "pass axis_names=")
        else:
            axes = tuple(axis_names)
            if len(axes) != len(shape):
                raise ValueError(
                    f"axis_names {axes} does not match mesh shape {shape}")
    import numpy as np
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        hint = "pass a smaller shape="
        if devices[0].platform == "cpu":
            hint = ("set XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{need} before any jax import, or " + hint)
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} "
            f"{devices[0].platform} devices — {hint}")
    return make_mesh(shape, axes, devices=devices[:need])


def agent_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that together form the paper's agent ring."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def agent_count(mesh) -> int:
    n = 1
    for ax in agent_axes(mesh):
        n *= mesh.shape[ax]
    return n


def model_axis(mesh) -> str:
    return "model"
