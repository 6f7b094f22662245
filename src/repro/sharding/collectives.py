"""Sparse consensus on the device mesh: any mixing matrix as ppermutes.

The consensus combine ``x_i <- sum_j M_ij x_j`` is realised without ever
materialising the (m, m) matrix on device: any doubly-stochastic ``M`` is
decomposed into per-*offset* permute rounds (``permute_schedule``).  For
offset ``o`` every agent receives the payload of agent ``(i + o) mod m``
via one ``lax.ppermute`` (a full cyclic shift is always a valid
permutation) and scales it by its own row weight ``M[i, (i+o) mod m]`` —
so ring, torus, and Erdős–Rényi / Metropolis graphs all run under
``shard_map``.  The ring mix of DESIGN.md §3 is the two-offset special
case (``ring_mix_tree`` below is now a thin wrapper).

Wire cost is O(n_offsets · |x|) per combine, where n_offsets is the
number of *distinct ring offsets* carrying any edge — NOT the per-agent
degree.  Structured graphs stay cheap (ring 2, torus 4-5); a dense-ish
Erdős–Rényi sample populates most offsets and can approach (m-1) · |x|,
worse than a ~2·|x| bandwidth-optimal all-reduce.  For such graphs
prefer ``impl="psum"`` (one all-reduce of an m-row contribution) or a
structured topology; the engine does not silently switch.

These helpers are the implementation layer of the ``ppermute`` consensus
backend (``repro/consensus/ppermute.py``); algorithms never call them
directly — they go through the ``ConsensusEngine`` API.  They must run
*inside* ``shard_map`` bodies whose ``axis_names`` contain only the agent
axes (the model axis stays auto and is partitioned by XLA as usual).

Backend options carried per-schedule rather than per-call:

* int8 compression — quantize the outgoing payload once per round, send
  (q, scale) per offset; halves (bf16) / quarters (f32) wire bytes.
* local-DP noise — Gaussian noise added to the *outgoing* payload before
  it leaves the agent; the local copy mixes un-noised, neighbours only
  ever see the noisy value.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


__all__ = [
    "PermuteSchedule", "PermuteWeights", "permute_schedule",
    "permute_mix_leaf", "permute_mix_tree", "ring_mix_tree",
    "ring_mix_leaf", "agent_index", "quantize_int8", "dequantize_int8",
]


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8 quantization (compressed consensus).

    The paper's conclusion names communication compression as the natural
    extension; this halves (bf16) or quarters (f32) the consensus wire
    bytes at the cost of a bounded quantization error that gradient
    tracking absorbs like any other consensus perturbation.
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _axis_name(agent_axes: Sequence[str]):
    return tuple(agent_axes) if len(agent_axes) > 1 else agent_axes[0]


def agent_index(agent_axes: Sequence[str]) -> jax.Array:
    return jax.lax.axis_index(_axis_name(agent_axes))


@dataclasses.dataclass(frozen=True)
class PermuteSchedule:
    """A mixing matrix decomposed into cyclic-shift permute rounds.

    Attributes:
      num_agents:   m.
      offsets:      ring offsets o with any nonzero weight; one ppermute
                    (full cyclic shift by o) is issued per entry.
      weights:      (n_offsets, m) — ``weights[k, i] = M[i, (i+offsets[k]) % m]``,
                    the weight agent i applies to the payload it receives
                    in round k (zero where the graph has no edge).
      self_weights: (m,) — the diagonal ``M[i, i]``.
    """

    num_agents: int
    offsets: tuple[int, ...]
    weights: np.ndarray
    self_weights: np.ndarray
    matrix: np.ndarray

    @property
    def rounds_per_mix(self) -> int:
        """ppermutes per consensus combine (the wire-cost multiplier)."""
        return len(self.offsets)


class PermuteWeights(NamedTuple):
    """One round's weights on the *shared* offset schedule.

    The time-varying topology layer (docs/TOPOLOGY.md) batches matrix
    streams on the ppermute backend as the ROADMAP describes: the
    offsets stay those of the base schedule (one ppermute per offset,
    program shape unchanged) and only the per-round weights vary — a
    dropped edge is a zero weight on its offset.  Passed per call as the
    ``override`` of ``permute_mix_leaf`` / ``permute_mix_tree``.

    Attributes:
      weights:      (n_offsets, m) — replaces ``schedule.weights``.
      self_weights: (m,) — replaces ``schedule.self_weights``.
      matrix:       (m, m) — replaces ``schedule.matrix`` (psum impl).
    """

    weights: jax.Array
    self_weights: jax.Array
    matrix: jax.Array


def permute_schedule(mixing, tol: float = 1e-12) -> PermuteSchedule:
    """Decompose any (sparse or dense) mixing matrix into ppermute rounds.

    ``mixing`` is a ``repro.core.consensus.MixingSpec`` or a raw (m, m)
    matrix (duck-typed on ``.matrix`` to keep this module free of core
    imports).  Offsets whose weight vector is identically ~0 are dropped,
    so *offset-structured* topologies pay few rounds (ring 2, 2-D torus
    4-5); an unstructured Erdős–Rényi graph usually populates most of the
    m - 1 offsets — see the module docstring for the cost trade-off.
    """
    mat = np.asarray(getattr(mixing, "matrix", mixing), dtype=np.float64)
    m = mat.shape[0]
    idx = np.arange(m)
    offsets, weights = [], []
    for o in range(1, m):
        w = mat[idx, (idx + o) % m]
        if np.max(np.abs(w)) > tol:
            offsets.append(o)
            weights.append(w)
    return PermuteSchedule(
        num_agents=m,
        offsets=tuple(offsets),
        weights=(np.stack(weights) if weights else np.zeros((0, m))),
        self_weights=np.diag(mat).copy(),
        matrix=mat,
    )


def _outgoing_payload(x, i, dp_sigma, dp_key, leaf_index=0):
    """What this agent shares: the iterate, optionally DP-noised.

    ``dp_sigma > 0`` without a key is a loud error: a caller that wants
    an un-noised combine (e.g. the u-mix) must pass ``dp_sigma=0``
    explicitly — silently skipping the noise would be a privacy loss.

    The key folds in BOTH the agent index and the leaf index: same-shaped
    leaves must receive independent noise, otherwise a neighbour could
    difference two leaves and cancel the noise exactly.
    """
    if dp_sigma > 0.0:
        if dp_key is None:
            raise ValueError("dp_sigma requires dp_key")
        key = jax.random.fold_in(jax.random.fold_in(dp_key, leaf_index), i)
        noise = dp_sigma * jax.random.normal(key, x.shape, jnp.float32)
        return (x.astype(jnp.float32) + noise).astype(x.dtype)
    return x


def _ppermute_mix(x, name, m, schedule, i, compress, dp_sigma, dp_key,
                  leaf_index=0, payload=None, override=None):
    """Per-offset cyclic-shift rounds: the wire-frugal realisation.

    ``payload`` (when given) replaces ``x`` as the outgoing value — the
    consensus engine's error-feedback layer hands in the already-
    compressed (decoded-value) payload here, so the legacy ``compress``
    quantization is skipped for it.  The accumulator is seeded with the
    *clean* local ``x`` either way: the agent's own term never round-trips
    through the wire format.

    ``override`` (a ``PermuteWeights``) replaces the schedule's weights
    for this round — same offsets, per-step values — which is how
    time-varying topologies run here without changing the program shape.
    """
    sw = (override.self_weights if override is not None
          else jnp.asarray(schedule.self_weights, jnp.float32))
    self_w = sw[i]
    acc = self_w * x.astype(jnp.float32)
    if not schedule.offsets:
        return acc.astype(x.dtype)

    payload = _outgoing_payload(x if payload is None else payload,
                                i, dp_sigma, dp_key, leaf_index)
    if compress == "int8":
        q, scale = quantize_int8(payload)

    weights = (override.weights if override is not None
               else jnp.asarray(schedule.weights, jnp.float32))
    for k, o in enumerate(schedule.offsets):
        # Destination j receives the payload of agent (j + o) mod m.
        perm = [((j + o) % m, j) for j in range(m)]
        if compress == "int8":
            recv = dequantize_int8(jax.lax.ppermute(q, name, perm),
                                   jax.lax.ppermute(scale, name, perm))
        else:
            recv = jax.lax.ppermute(payload, name, perm)
        acc = acc + weights[k, i] * recv.astype(jnp.float32)
    return acc.astype(x.dtype)


def _psum_mix(x, name, m, schedule, i, compress, dp_sigma, dp_key,
              leaf_index=0, payload=None, override=None):
    """All-reduce realisation: agent j contributes M[:, j] (x) sent_j and
    everyone slices its own row of the psum.

    Costs one m-times-payload all-reduce instead of per-edge exchanges,
    but preserves the exact mixing semantics — including that
    the agent's *own* term mixes the clean local iterate while neighbours
    see the compressed / noised payload.  ``payload`` overrides the
    outgoing value (pre-compressed by the engine's error-feedback layer);
    the existing self-weight correction then yields exactly
    ``mix(payload) + M_ii (x - payload)``.
    """
    payload = _outgoing_payload(x if payload is None else payload,
                                i, dp_sigma, dp_key, leaf_index)
    if compress == "int8":
        q, scale = quantize_int8(payload)
        sent = dequantize_int8(q, scale)  # what neighbours decode
    else:
        sent = payload.astype(jnp.float32)

    mat = (override.matrix if override is not None
           else jnp.asarray(schedule.matrix, jnp.float32))
    col = mat[:, i].reshape((m,) + (1,) * x.ndim)
    mixed = jax.lax.psum(col * sent[None], name)[i]
    # The psum applied M_ii to the *shared* payload; the local copy mixes
    # un-noised / un-quantized.
    sw = (override.self_weights if override is not None
          else jnp.asarray(schedule.self_weights, jnp.float32))
    self_w = sw[i]
    mixed = mixed + self_w * (x.astype(jnp.float32) - sent)
    return mixed.astype(x.dtype)


def permute_mix_leaf(x: jax.Array, agent_axes: Sequence[str],
                     schedule: PermuteSchedule,
                     compress: str | None = None,
                     dp_sigma: float = 0.0,
                     dp_key: jax.Array | None = None,
                     impl: str = "ppermute",
                     agent_index: jax.Array | None = None,
                     leaf_index: int = 0,
                     payload: jax.Array | None = None,
                     override: PermuteWeights | None = None) -> jax.Array:
    """One consensus combine of a per-agent leaf (inside shard_map).

    compress="int8": send int8-quantized payloads (+ scalar scale).
    dp_sigma > 0 with dp_key set: Gaussian noise on the outgoing payload
    (local differential privacy on shared iterates); the local copy is
    mixed un-noised.
    impl: "ppermute" (per-edge exchanges) or "psum" (all-reduce fallback
    for partially-auto bodies on old JAX).
    agent_index: this agent's ring position; defaults to
    ``lax.axis_index``, but partially-auto old-JAX bodies must thread it
    in as data (partition-id does not lower there).
    payload: override for the outgoing value (the engine's error-feedback
    layer passes the pre-compressed payload here; DP noise still applies
    to it, the local copy still mixes clean).
    override: this round's ``PermuteWeights`` — per-step weights on the
    shared offset schedule (time-varying topologies, docs/TOPOLOGY.md).
    """
    name = _axis_name(agent_axes)
    m = jax.lax.axis_size(name)
    if m != schedule.num_agents:
        raise ValueError(
            f"schedule built for m={schedule.num_agents} but the agent "
            f"axes {tuple(agent_axes)} have size {m}")
    i = (jax.lax.axis_index(name) if agent_index is None
         else agent_index)
    mix = _psum_mix if impl == "psum" else _ppermute_mix
    return mix(x, name, m, schedule, i, compress, dp_sigma, dp_key,
               leaf_index, payload, override)


def permute_mix_tree(tree, agent_axes: Sequence[str],
                     schedule: PermuteSchedule,
                     compress: str | None = None, dp_sigma: float = 0.0,
                     dp_key: jax.Array | None = None,
                     impl: str = "ppermute",
                     agent_index: jax.Array | None = None,
                     payload_tree=None,
                     override: PermuteWeights | None = None):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    payloads = (jax.tree_util.tree_flatten(payload_tree)[0]
                if payload_tree is not None else [None] * len(leaves))
    mixed = [permute_mix_leaf(l, agent_axes, schedule,
                              compress=compress, dp_sigma=dp_sigma,
                              dp_key=dp_key, impl=impl,
                              agent_index=agent_index, leaf_index=k,
                              payload=pl, override=override)
             for k, (l, pl) in enumerate(zip(leaves, payloads))]
    return jax.tree_util.tree_unflatten(treedef, mixed)


def ring_mix_leaf(x: jax.Array, agent_axes: Sequence[str],
                  self_weight: float, compress: str | None = None,
                  dp_sigma: float = 0.0,
                  dp_key: jax.Array | None = None,
                  leaf_index: int = 0) -> jax.Array:
    """Ring special case: the schedule of ``ring_mixing(m, self_weight)``."""
    from repro.core.consensus import ring_mixing  # lazy: avoids core cycle
    name = _axis_name(agent_axes)
    m = jax.lax.axis_size(name)
    schedule = permute_schedule(ring_mixing(m, self_weight=self_weight))
    return permute_mix_leaf(x, agent_axes, schedule, compress=compress,
                            dp_sigma=dp_sigma, dp_key=dp_key,
                            leaf_index=leaf_index)


def ring_mix_tree(tree, agent_axes: Sequence[str], self_weight: float,
                  compress: str | None = None, dp_sigma: float = 0.0,
                  dp_key: jax.Array | None = None):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    mixed = [ring_mix_leaf(l, agent_axes, self_weight,
                           compress=compress, dp_sigma=dp_sigma,
                           dp_key=dp_key, leaf_index=k)
             for k, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, mixed)
