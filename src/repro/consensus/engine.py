"""The ConsensusEngine API: one pluggable backend behind every update path.

The paper's communication result hinges on a single primitive — the
consensus combine ``x_i <- sum_j M_ij x_j`` (eqs. 6/10).  Every INTERACT
variant (Algorithm 1, SVR-INTERACT, GT-DSGD, D-SGD, and the distributed LM
train step) expresses its Steps 1/3 through this API instead of carrying
its own copy of the combine:

    engine.mix(tree) -> tree
        The bare combine applied leaf-wise (leading agent dim m on the
        dense/pallas backends; the local agent's slice under shard_map on
        the ppermute backend).

    engine.step1_step3(x, u, p, p_prev, alpha) -> (x_new, u_new)
        The fused pair the algorithms actually need:
            x_new = mix(x) - alpha * u          (Step 1, eq. 6)
            u_new = mix(u) + (p - p_prev)       (Step 3, eq. 10)
        The base implementation composes two ``mix`` calls; the pallas
        backend overrides it with one fused kernel launch.

    engine.mix_ef(tree, ef, t) -> (tree, ef)
        The wire-aware combine: compress each agent's outgoing
        *innovation* against a gossip-tracked public copy with error
        feedback (``repro/consensus/compress``), honour the
        warmup-then-compress schedule and the communication interval,
        and return the updated wire state ``{"e": residual, "ref":
        public copy}`` alongside the mixed values.  With ``ef=None``
        and an inactive wire config it is exactly ``(mix(tree), None)``.

    engine.bytes_on_wire(tree) -> int
        Wire bytes ONE agent ships for ONE combine of a per-agent
        payload shaped like ``tree`` (no agent dim) under the engine's
        compressor — the accounting behind bytes-per-unit-stationarity.

Wire options (every backend): ``compression`` is a
``repro.consensus.compress.CompressionConfig``; ``communication_interval
= k`` mixes only on steps with ``t % k == 0`` (local descent in
between), realised as a ``jnp.where`` on the step index so the program
stays one compile.  When compression uses error feedback the solver
carries the residual pytree in its scan state (``ef`` fields on the
state NamedTuples), threaded through ``consensus_descent_and_track``.

Backends (see ``make_engine``):

    dense     (m, m) matmul reference — any topology, single host.
    pallas    fused consensus+tracking Pallas kernel — any topology,
              single host, the m-agent simulator's hot loop.
    ppermute  per-offset ``lax.ppermute`` schedule — any sparse symmetric
              topology, runs inside ``shard_map`` on the device mesh.
    allgather dense combine inside ``shard_map`` — ``lax.all_gather``
              the peer rows, dot the local rows of the full matrix; any
              topology (including traced matrix streams) on the mesh.

``register_backend`` is the extension point: a fifth backend is one
decorated factory.  Engines optionally carry a ``CommsLedger``
(``repro.consensus.ledger``) that records *measured* per-round wire
bytes at trace time — ``attach_ledger(engine, ...)`` before building
the step.

``consensus_descent_and_track`` is the shared step-core: the full Steps
1-3 skeleton (consensus + descent, local gradients via a callback,
gradient tracking) used by interact / svr_interact / baselines / the
distributed train steps, so the algorithm files only differ in how they
estimate the local gradients.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro import tracing
from repro.byzantine import (ByzantineConfig, apply_attack, byzantine_mask,
                             make_attack, robust_combine)
from repro.consensus.compress import CompressionConfig, make_compressor

__all__ = [
    "ConsensusEngine", "MeshBackendMixin", "as_engine", "make_engine",
    "register_backend", "BACKENDS", "consensus_descent_and_track",
]


def _f32(leaf):
    return leaf.astype(jnp.float32)


# wire streams an INTERACT-family round ships, keyed for the per-stream
# attack derivation (the inner iterate y never crosses the wire).
_STREAM_IDS = {"x": 0, "u": 1}


class ConsensusEngine:
    """Base class: a consensus combine plus the fused Step-1/3 pair."""

    name = "base"

    # time-varying topology runtime (repro.topology.runtime), installed
    # by ``attach_topology``; None = the fixed-matrix path, bit for bit.
    topology = None

    # ghost-pad active-agent count (padded sweeps install a traced value
    # so the Byzantine mask never selects a ghost slot); None = all m.
    num_active = None

    # measured-communication ledger (repro.consensus.ledger), installed
    # by ``attach_ledger`` BEFORE the step is traced; None = no
    # accounting, zero trace cost.
    ledger = None

    def _configure_wire(self, compression: CompressionConfig | None = None,
                        communication_interval: int = 1,
                        byzantine: ByzantineConfig | None = None):
        """Install the wire options every backend carries (call from
        ``__init__``): the compressor, the mix cadence, and the
        Byzantine attack/combine configuration."""
        self.compression = compression or CompressionConfig()
        self.compressor = make_compressor(self.compression)
        self.communication_interval = int(communication_interval)
        if self.communication_interval < 1:
            raise ValueError("communication_interval must be >= 1, got "
                             f"{communication_interval}")
        if not 0.0 < self.compression.gamma <= 1.0:
            raise ValueError("compression.gamma must be in (0, 1], got "
                             f"{self.compression.gamma}")
        self.byzantine = byzantine or ByzantineConfig()
        mat = getattr(self, "matrix", None)
        if mat is not None:
            # loud breakdown / capacity errors against the known m
            # (shape is static even for traced padded matrices)
            self.byzantine.validate_for(int(mat.shape[0]))
        # attack operands: concrete here, overridden with traced sweep
        # operands by the padded batching path (num_byzantine / scale /
        # the schedule key are vmap batch axes there).
        if self.byzantine.attack_active:
            self.byz_values = {
                "num_byzantine": self.byzantine.num_byzantine,
                "scale": self.byzantine.scale,
                "key": jax.random.PRNGKey(self.byzantine.resolve_seed(0)),
            }
        else:
            self.byz_values = None

    def _damp(self, mixed, tree):
        """CHOCO consensus stepsize: ``x + gamma * (mixed - x)``."""
        g = self.compression.gamma
        if g == 1.0:
            return mixed
        return jax.tree_util.tree_map(
            lambda mx, xx: (g * _f32(mx) + (1.0 - g) * _f32(xx)
                            ).astype(mx.dtype), mixed, tree)

    @property
    def wire_active(self) -> bool:
        """Does this engine need the (t, ef) wire path at all?

        Byzantine options ride the wire path too: attacks corrupt the
        shipped payload and robust rules replace the combine, both of
        which live in ``mix_ef`` (this is also what routes the pallas
        fast path through the base composition).
        """
        return (self.compression.active
                or self.communication_interval != 1
                or self.byzantine.active)

    # -- Byzantine layer: payload corruption + robust aggregation ---------

    def _attack_payload(self, tree, t, stream: str):
        """Corrupt the Byzantine slots' outgoing payload for ``stream``.

        A python no-op (bitwise, zero trace cost) when no attack is
        configured or the attack does not touch this stream.  The mask
        is the fixed seeded subset of :func:`repro.byzantine.
        byzantine_mask`; the per-round key folds (stream, t) into the
        schedule key so re-runs replay the identical corruption.
        """
        byz = self.byzantine
        if not byz.attack_active:
            return tree
        attack = make_attack(byz.kind)
        if stream not in attack.streams:
            return tree
        vals = self.byz_values
        m = jax.tree_util.tree_leaves(tree)[0].shape[0]
        mask = byzantine_mask(vals["key"], m, vals["num_byzantine"],
                              num_active=self.num_active)
        key_t = jax.random.fold_in(
            jax.random.fold_in(vals["key"], _STREAM_IDS[stream]),
            self._require_t(t))
        return apply_attack(attack, tree, mask, key_t, vals["scale"])

    def _combine(self, tree, *, matrix=None, dp_key=None, agent_index=None):
        """The configured aggregation: ``mix`` for ``weighted``, else a
        robust rule over the mixing row's support (dense rows only)."""
        rule = self.byzantine.combine
        if rule == "weighted":
            return self.mix(tree, matrix=matrix, dp_key=dp_key,
                            agent_index=agent_index)
        mat = matrix if matrix is not None else getattr(self, "matrix",
                                                        None)
        if mat is None:
            raise NotImplementedError(
                f"combine rule {rule!r} needs all-to-all access to the "
                f"payload rows, but the {self.name!r} backend holds no "
                f"full mixing matrix — run robust rules on the dense "
                f"backend (pallas routes there automatically)")
        return robust_combine(mat, tree, rule,
                              self.byzantine.resolve_trim())

    def mix(self, tree, *, matrix=None, dp_key: jax.Array | None = None,
            agent_index: jax.Array | None = None):
        """Apply ``x_i <- sum_j M_ij x_j`` to every leaf of ``tree``.

        ``matrix`` overrides the engine's fixed mixing matrix for this
        call (the per-step matrix of a time-varying topology; on the
        ppermute backend a ``PermuteWeights`` override on the shared
        offset schedule).  ``dp_key`` (backends that support it) keys
        the local-DP noise on the outgoing payload; ``agent_index``
        threads the agent's ring position into distributed backends that
        cannot derive it from the mesh.  Single-host backends ignore
        both.
        """
        raise NotImplementedError

    def topology_matrix(self, t, tree=None):
        """The round's mixing-matrix override, or None on the fixed path.

        With a time-varying topology attached (``engine.topology``), the
        matrix stream is a function of the step index — gathering
        ``matrices[t % T]`` inside the scan keeps the whole run one
        compile.  The adaptive process additionally reads the current
        iterates (``tree``).
        """
        if self.topology is None:
            return None
        if t is None:
            raise ValueError(
                "a time-varying topology needs the step index: pass t= "
                "to mix_ef / step1_step3 (or resolve the matrix yourself "
                "via engine.topology_matrix(t) and pass matrix=)")
        return self.topology.matrix_at(t, tree)

    # -- measured wire accounting (repro.consensus.ledger) ----------------

    def _ledger_note(self, stream: str, tree) -> None:
        """Record ``stream``'s per-round wire template on the ledger.

        Called at trace time from every combine entry point; a python
        no-op (zero trace cost) without an attached ledger.  The matrix
        backends ship ONE concatenated per-agent buffer per stream per
        round — exactly what ``bytes_on_wire`` prices — so measured and
        priced bytes agree bit for bit here; ppermute overrides this
        with its per-leaf x permute-rounds template.
        """
        led = self.ledger
        if led is None:
            return
        from repro.consensus.ledger import StreamRecord
        leaves = jax.tree_util.tree_leaves(tree)
        m = int(leaves[0].shape[0]) if leaves[0].ndim else 1
        size = sum(int(l.size) for l in leaves) // max(1, m)
        led.note(stream, StreamRecord(
            op=self.name, entries=size,
            wire_bytes=int(self.compressor.bytes_on_wire(size)),
            full_bytes=4 * size, collectives=1))

    # -- the wire path: EF compression + warmup + interval ----------------

    def _self_weights(self, matrix=None) -> jax.Array:
        """Per-agent self weights M[i, i] (matrix-holding backends)."""
        mat = self.matrix if matrix is None else matrix
        return jnp.diagonal(mat).astype(jnp.float32)

    def _require_t(self, t):
        if t is None:
            raise ValueError(
                "the warmup schedule / communication interval need the "
                "step index: pass t= to mix_ef / step1_step3")
        return jnp.asarray(t)

    def _compress_payload(self, tree, ef, t):
        """Per-agent compression of the (m, ...) raveled buffer.

        Returns ``(payload_tree, ef_new)`` where ``payload_tree`` is the
        value the neighbours decode (leaf dtype, leaf-shaped).  Each
        agent's leaves are flattened and concatenated into one (m, D)
        buffer and compressed row-wise — one wire payload per agent per
        combine, and (because rows compress independently) bitwise
        invariant under ghost-agent padding.

        With wire state ``ef = {"e": residual, "ref": public copy}`` the
        agent transmits the compressed innovation ``c = C(x - ref)`` and
        everyone reconstructs ``payload = ref + c`` (CHOCO-style).  The
        feedback is intrinsic: ``ref`` advances only by what was
        actually transmitted, so the residual ``e = (x - ref) - c`` is
        automatically part of the NEXT innovation (``x' - ref' =
        (x' - x) + e``) — adding ``e`` explicitly would double-count it
        and provably diverges for hard-sparsifying wires.  ``ef_new``
        carries the updated residual (diagnostic) and the advanced
        public copy.  With ``ef=None`` the raw value is compressed
        uncompensated (``payload = C(x)``) — no memory, errors are
        never re-sent.
        """
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        m = leaves[0].shape[0]
        sizes = [int(l.size) // m for l in leaves]
        concat = lambda tr: jnp.concatenate(
            [_f32(l).reshape(m, -1)
             for l in jax.tree_util.tree_flatten(tr)[0]], axis=1)

        def split(buf, dtypes=None):
            parts = jnp.split(buf, _split_points(sizes), axis=1)
            return jax.tree_util.tree_unflatten(
                treedef,
                [p.reshape(l.shape) if dtypes is None
                 else p.reshape(l.shape).astype(l.dtype)
                 for p, l in zip(parts, leaves)])

        buf = concat(tree)
        if ef is not None:
            ref = concat(ef["ref"])
            v = buf - ref
        else:
            ref = jnp.zeros_like(buf)
            v = buf
        c = jax.vmap(self.compressor.encode_decode)(v)
        if self.compression.compress_after > 0:
            warm = self._require_t(t) < self.compression.compress_after
            c = jnp.where(warm, v, c)
        payload = ref + c
        ef_new = None
        if ef is not None:
            ef_new = {"e": split(v - c), "ref": split(payload)}
        return split(payload, dtypes=True), ef_new

    def _apply_interval(self, t, mixed, tree, ef_new, ef):
        """Skip the combine on steps with ``t % interval != 0``.

        The mixed values fall back to the un-mixed local ones (so Step 1
        degrades to plain local descent) and the wire state freezes —
        nothing was sent, so no compression error was incurred and no
        public copy advanced.
        """
        k = self.communication_interval
        if k == 1:
            return mixed, ef_new
        do = (self._require_t(t) % k) == 0
        pick = lambda a, b: jax.tree_util.tree_map(
            lambda aa, bb: jnp.where(do, aa, bb), a, b)
        mixed = pick(mixed, tree)
        if ef is not None:
            ef_new = pick(ef_new, ef)
        return mixed, ef_new

    def mix_ef(self, tree, ef=None, t=None, *, matrix=None,
               dp_key: jax.Array | None = None,
               agent_index: jax.Array | None = None, stream: str = "x"):
        """The wire-aware combine: ``(mixed, ef_new)``.

        ``ef`` is this stream's wire state ``{"e": EF residual, "ref":
        public copy}`` (``None`` when compression is off or
        uncompensated).  The reconstructed payload ``ref + C(x - ref +
        e)`` is what neighbours combine; the agent's own term mixes the
        clean local value (``mix(payload) + M_ii (x - payload)``) — the
        same self-clean semantics as the ppermute int8/DP wire.  With an
        inactive wire config this is exactly ``(mix(tree), ef)``.
        ``matrix`` (or an attached time-varying topology, resolved from
        ``t``) overrides the fixed matrix for this round.

        ``stream`` labels which wire stream this combine carries
        (``"x"``/``"u"``) so stream-selective attacks corrupt the right
        payload.  Corruption happens *before* compression: the CHOCO
        ``ref`` copies advance by what was actually transmitted, so a
        Byzantine ``ref`` stream never poisons honest agents'
        reconstruction of each other.  The self-clean correction applies
        only under the ``weighted`` rule — the robust rules are
        nonlinear and have no exact self term (docs/BYZANTINE.md).
        """
        if matrix is None:
            matrix = self.topology_matrix(t, tree)
        self._ledger_note(stream, tree)
        sent = self._attack_payload(tree, t, stream)
        if self.compression.active:
            payload, ef_new = self._compress_payload(sent, ef, t)
            mixed = self._combine(payload, matrix=matrix, dp_key=dp_key,
                                  agent_index=agent_index)
            if self.byzantine.combine == "weighted":
                d = self._self_weights(matrix)
                mixed = jax.tree_util.tree_map(
                    lambda mx, xx, cc: (
                        _f32(mx) + d.reshape((-1,) + (1,) * (mx.ndim - 1))
                        * (_f32(xx) - _f32(cc))).astype(mx.dtype),
                    mixed, tree, payload)
            mixed = self._damp(mixed, tree)
        else:
            mixed = self._combine(sent, matrix=matrix, dp_key=dp_key,
                                  agent_index=agent_index)
            ef_new = ef
        return self._apply_interval(t, mixed, tree, ef_new, ef)

    def bytes_on_wire(self, tree) -> int:
        """Wire bytes ONE agent ships for ONE combine of ``tree``.

        ``tree`` is a per-agent payload (no agent dim).  Matrix backends
        ship one compressed buffer of all leaves concatenated; warmup /
        interval scheduling is NOT folded in here (see
        ``repro.consensus.compress.cumulative_wire_bytes``).
        """
        size = sum(int(l.size) for l in jax.tree_util.tree_leaves(tree))
        return self.compressor.bytes_on_wire(size)

    def step1_step3(self, x, u, p, p_prev, alpha: float, *,
                    t=None, ef=None, matrix=None,
                    dp_key: jax.Array | None = None,
                    agent_index: jax.Array | None = None):
        """Fused eq. (6) + eq. (10).

        Returns ``(x_new, u_new)`` on the legacy full-precision path
        (``ef is None`` and no wire options configured), and ``(x_new,
        u_new, ef_new)`` on the wire path, where ``ef`` / ``ef_new`` is
        the per-stream wire-state dict ``{"x": {"e", "ref"}, "u":
        {...}}`` (or ``None`` for uncompensated compression / bare
        intervals).

        Math runs in float32 and is cast back to the leaf dtype, so bf16
        states mix without drift.  The tracking difference is grouped as
        ``mix(u) + (p - p_prev)`` so calling with ``p is p_prev`` yields
        ``mix(u)`` exactly (how the step-core obtains the mixed tracker
        before the new gradients exist).
        """
        if matrix is None:
            matrix = self.topology_matrix(t, x)
        wire = ef is not None or self.wire_active
        if wire:
            x_mixed, ef_x = self.mix_ef(
                x, None if ef is None else ef.get("x"), t,
                matrix=matrix, dp_key=dp_key, agent_index=agent_index,
                stream="x")
            u_mixed, ef_u = self.mix_ef(
                u, None if ef is None else ef.get("u"), t,
                matrix=matrix, agent_index=agent_index, stream="u")
        else:
            self._ledger_note("x", x)
            self._ledger_note("u", u)
            x_mixed = self.mix(x, matrix=matrix, dp_key=dp_key,
                               agent_index=agent_index)
            u_mixed = self.mix(u, matrix=matrix, agent_index=agent_index)
        x_new = jax.tree_util.tree_map(
            lambda mx, uu: (_f32(mx) - alpha * _f32(uu)).astype(mx.dtype),
            x_mixed, u)
        u_new = jax.tree_util.tree_map(
            lambda mu, pn, pp: (_f32(mu) + (_f32(pn) - _f32(pp))
                                ).astype(mu.dtype),
            u_mixed, p, p_prev)
        if not wire:
            return x_new, u_new
        ef_new = None if ef is None else {"x": ef_x, "u": ef_u}
        return x_new, u_new, ef_new


class MeshBackendMixin:
    """Shared helpers for backends that run *inside* ``shard_map``.

    Mesh backends (ppermute, allgather) see only the local agent's slice
    (leading local dim) and must recover global slot identities from the
    mesh axes — for Byzantine masks/keys that have to match the dense
    reference bitwise, and for slicing the local rows of a full mixing
    matrix.  Requires ``self.agent_axes`` and the usual wire attributes
    from ``_configure_wire``; ``_mesh_num_agents`` supplies the global
    agent count (schedule / matrix dependent).
    """

    @property
    def _mesh_num_agents(self) -> int:
        raise NotImplementedError

    def _axis_agent_index(self):
        """This shard's position along the (flattened) agent axes."""
        idx = jnp.int32(0)
        for ax in self.agent_axes:
            idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
        return idx

    def _local_slots(self, tree, agent_index):
        """Global slot ids of this shard's rows (leading local dim)."""
        rows = jax.tree_util.tree_leaves(tree)[0].shape[0]
        if agent_index is None:
            idx = self._axis_agent_index()
        else:
            idx = jnp.asarray(agent_index, jnp.int32)
        return idx * rows + jnp.arange(rows, dtype=jnp.int32)

    def _attack_local(self, tree, t, stream, agent_index):
        """The local-slice form of the base ``_attack_payload``.

        The mask and per-slot keys are derived from *global* slot ids,
        so the corrupted payload matches the dense reference bitwise
        (under the exact ``none`` compressor).  Expects the standard
        leading local agent dim on every leaf.
        """
        byz = self.byzantine
        if not byz.attack_active:
            return tree
        attack = make_attack(byz.kind)
        if stream not in attack.streams:
            return tree
        vals = self.byz_values
        mask = byzantine_mask(vals["key"], self._mesh_num_agents,
                              vals["num_byzantine"],
                              num_active=self.num_active)
        slots = self._local_slots(tree, agent_index)
        key_t = jax.random.fold_in(
            jax.random.fold_in(vals["key"], _STREAM_IDS[stream]),
            self._require_t(t))
        return apply_attack(attack, tree, mask[slots], key_t,
                            vals["scale"], slots=slots)


def _split_points(sizes):
    """Split points for ``jnp.split`` from a list of leaf sizes."""
    out, acc = [], 0
    for s in sizes[:-1]:
        acc += s
        out.append(acc)
    return out


def consensus_descent_and_track(
    engine: ConsensusEngine,
    x, y, u, v, p_prev,
    alpha: float, beta: float,
    grads_fn: Callable,
    *,
    t=None,
    ef=None,
    dp_key: jax.Array | None = None,
    agent_index: jax.Array | None = None,
):
    """One INTERACT iteration skeleton shared by every tracking algorithm.

      Step 1: x_new = mix(x) - alpha u ;  y_new = y - beta v
      Step 2: (p_new, v_new, aux) = grads_fn(x_new, y_new)
      Step 3: u_new = mix(u) + p_new - p_prev

    Both mixes are issued through one ``engine.step1_step3`` call (with
    ``p = p_prev`` its tracking term vanishes and it returns exactly
    ``(x_new, mix(u))``), so the pallas backend fuses them into a single
    kernel launch; the tracking correction is applied element-wise once
    the new local gradients exist.

    ``grads_fn(x_new, y_new) -> (p_new, v_new, aux)``; ``aux`` is passed
    through untouched (metrics, or None).

    ``t`` (the step index) and ``ef`` (the per-stream wire-state dict
    ``{"x": {"e", "ref"}, ...}``, or ``None``) drive the engine's wire
    path — compression, warmup schedule, communication interval; both
    live in the solver's scan carry.  With an inactive wire config they
    pass straight through.

    Returns ``(x_new, y_new, u_new, v_new, p_new, ef_new, aux)``.
    """
    wire = ef is not None or getattr(engine, "wire_active", False)
    with tracing.scope(tracing.MIX):
        if wire:
            x_new, u_mixed, ef_new = engine.step1_step3(
                x, u, p_prev, p_prev, alpha, t=t, ef=ef, dp_key=dp_key,
                agent_index=agent_index)
        else:
            x_new, u_mixed = engine.step1_step3(x, u, p_prev, p_prev, alpha,
                                                t=t, dp_key=dp_key,
                                                agent_index=agent_index)
            ef_new = ef
    y_new = jax.tree_util.tree_map(
        lambda yy, vv: (_f32(yy) - beta * _f32(vv)).astype(yy.dtype), y, v)

    with tracing.scope(tracing.LOCAL_GRAD):
        p_new, v_new, aux = grads_fn(x_new, y_new)

    u_new = jax.tree_util.tree_map(
        lambda mu, pn, pp: (_f32(mu) + (_f32(pn) - _f32(pp))
                            ).astype(mu.dtype),
        u_mixed, p_new, p_prev)
    return x_new, y_new, u_new, v_new, p_new, ef_new, aux


# Backend registry: name -> factory(mixing, **opts).  Factories import
# their engine module lazily (PEP-562 in the package __init__) so pulling
# in repro.core never loads the pallas extras or the sharding collectives.
BACKENDS: dict[str, Callable] = {}


def register_backend(name: str) -> Callable[[Callable], Callable]:
    """Decorator: register a consensus-backend factory under ``name``.

    The factory signature is ``factory(mixing, **opts) ->
    ConsensusEngine``; ``make_engine`` resolves names through this
    registry, so adding a backend is one decorated factory — no edits to
    the engine module required (the in-repo backends register here only
    to keep their imports lazy).
    """

    def deco(factory: Callable) -> Callable:
        existing = BACKENDS.get(name)
        if existing is not None and existing is not factory:
            raise ValueError(f"consensus backend {name!r} already "
                             f"registered ({existing!r})")
        BACKENDS[name] = factory
        return factory

    return deco


@register_backend("dense")
def _make_dense(mixing, **opts):
    from repro.consensus.dense import DenseEngine
    return DenseEngine(mixing, **opts)


@register_backend("pallas")
def _make_pallas(mixing, **opts):
    from repro.consensus.pallas import PallasEngine
    return PallasEngine(mixing, **opts)


@register_backend("ppermute")
def _make_ppermute(mixing, **opts):
    from repro.consensus.ppermute import PermuteEngine
    return PermuteEngine(mixing, **opts)


@register_backend("allgather")
def _make_allgather(mixing, **opts):
    from repro.consensus.allgather import AllGatherEngine
    return AllGatherEngine(mixing, **opts)


def make_engine(backend: str, mixing, **opts) -> ConsensusEngine:
    """Build a consensus backend by name.

    ``mixing`` is a ``MixingSpec`` or a raw (m, m) matrix.  Backend
    options: ``interpret`` (pallas), ``agent_axes``/
    ``compress``/``dp_sigma`` (ppermute), ``agent_axes`` (allgather);
    every backend additionally accepts ``compression``/
    ``communication_interval``/``byzantine`` wire options.
    """
    try:
        factory = BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown consensus backend {backend!r}; "
            f"choose from {sorted(BACKENDS)}") from None
    return factory(mixing, **opts)


def as_engine(mixing_or_engine) -> ConsensusEngine:
    """Coerce a raw mixing matrix / MixingSpec to a dense engine."""
    if isinstance(mixing_or_engine, ConsensusEngine):
        return mixing_or_engine
    return _make_dense(mixing_or_engine)
