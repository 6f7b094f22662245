"""ppermute consensus backend: sparse topologies on the device mesh.

Decomposes any ``MixingSpec`` into per-offset cyclic-shift permute rounds
(``repro/sharding/collectives.permute_schedule``) so Erdős–Rényi /
Metropolis / torus graphs — not just the hard-coded ring — run under
``shard_map``.  Must be called from *inside* a shard_map body whose
manual axes are exactly ``agent_axes``; leaves are the local agent's
slice (leading local dim 1 in the train step, or unbatched in tests).

int8 wire compression and local-DP noise are backend options carried by
the engine, not ring-only kwargs: ``compress="int8"`` quantizes every
outgoing payload, ``dp_sigma > 0`` adds Gaussian noise to the payload
whenever a ``dp_key`` is supplied to ``mix`` (the x-mix passes one, the
u-mix does not — only shared iterates are privatized).

``impl="psum"`` selects the all-reduce realisation of the same matrix
(one m-times-payload all-reduce instead of per-edge exchanges); it needs
the agent index threaded in via ``mix(..., agent_index=...)``.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.consensus.compress import CompressionConfig, Int8Compressor
from repro.consensus.engine import ConsensusEngine, MeshBackendMixin
from repro.core.consensus import MixingSpec
from repro.sharding.collectives import (
    PermuteSchedule, permute_mix_tree, permute_schedule)

__all__ = ["PermuteEngine"]


class PermuteEngine(MeshBackendMixin, ConsensusEngine):

    name = "ppermute"

    def __init__(self, mixing: MixingSpec | PermuteSchedule,
                 agent_axes: Sequence[str] = ("data",),
                 compress: str | None = None, dp_sigma: float = 0.0,
                 impl: str = "ppermute",
                 compression: CompressionConfig | None = None,
                 communication_interval: int = 1, byzantine=None):
        self.schedule = (mixing if isinstance(mixing, PermuteSchedule)
                         else permute_schedule(mixing))
        self.agent_axes = tuple(agent_axes)
        self.compress = compress
        self.dp_sigma = float(dp_sigma)
        if impl not in ("ppermute", "psum"):
            raise ValueError(f"unknown ppermute impl {impl!r}")
        self.impl = impl
        self._configure_wire(compression, communication_interval, byzantine)
        if self.compression.active and compress is not None:
            raise ValueError(
                "pass either the legacy compress= wire format or a "
                "CompressionConfig, not both")
        self.byzantine.validate_for(self.schedule.num_agents)
        if self.byzantine.combine != "weighted":
            raise NotImplementedError(
                f"combine rule {self.byzantine.combine!r} needs "
                f"all-to-all access to the payload rows, but the "
                f"ppermute backend only ever holds the local agent's "
                f"slice — robust rules require the dense backend")

    @property
    def rounds_per_mix(self) -> int:
        return self.schedule.rounds_per_mix

    @property
    def _mesh_num_agents(self) -> int:
        return self.schedule.num_agents

    def mix(self, tree, *, matrix=None, dp_key=None, agent_index=None):
        # ``matrix`` here is a ``PermuteWeights`` override — the round's
        # weights on the SAME offset schedule (time-varying topology).
        return permute_mix_tree(
            tree, self.agent_axes, self.schedule, compress=self.compress,
            dp_sigma=self.dp_sigma if dp_key is not None else 0.0,
            dp_key=dp_key, impl=self.impl, agent_index=agent_index,
            override=matrix)

    def _ledger_note(self, stream, tree):
        """Per-link wire template: one payload per LEAF per permute round.

        This is the unicast model ``bytes_on_wire`` prices for this
        backend — ``rounds_per_mix`` permute rounds each shipping every
        leaf separately — which exceeds the matrix backends' broadcast
        model by the offset fan-out on non-ring graphs.  A dropped link
        in a time-varying topology zeroes a *weight*, not a payload: the
        compiled program still ships the round (static shapes), and so
        does the measured accounting — docs/DISTRIBUTED.md spells out
        the contrast with the per-process priced model.
        """
        led = self.ledger
        if led is None:
            return
        from repro.consensus.ledger import StreamRecord
        compressor = self.compressor
        if not self.compression.active and self.compress == "int8":
            compressor = Int8Compressor()
        leaves = jax.tree_util.tree_leaves(tree)
        sizes = [int(l.size) // (int(l.shape[0]) if l.ndim else 1)
                 for l in leaves]
        rounds = self.rounds_per_mix
        led.note(stream, StreamRecord(
            op=f"{self.name}/{self.impl}", entries=sum(sizes),
            wire_bytes=rounds * sum(compressor.bytes_on_wire(s)
                                    for s in sizes),
            full_bytes=rounds * 4 * sum(sizes),
            collectives=rounds * len(leaves)))

    def mix_ef(self, tree, ef=None, t=None, *, matrix=None, dp_key=None,
               agent_index=None, stream="x"):
        """Per-neighbour wire path: compress each outgoing *leaf*.

        Unlike the matrix backends (one compressed buffer of all leaves
        concatenated per agent), every leaf is a separate wire payload
        here — so scale granularity differs and cross-backend agreement
        is a tolerance contract, not bitwise (the ``none`` compressor is
        exact on both).  The wire state carries the same ``{"e", "ref"}``
        innovation scheme as the matrix backends: the agent ships
        ``C(x - ref)`` and peers (who track ``ref`` by replaying received
        innovations) reconstruct ``ref + C(...)`` — the
        reconstruction is the payload tree handed to the collectives
        layer; the local self term mixes the clean value by construction
        (``_ppermute_mix`` seeds the accumulator with it, ``_psum_mix``
        applies the self-weight correction).
        """
        if matrix is None:
            matrix = self.topology_matrix(t, tree)
        self._ledger_note(stream, tree)
        sent = self._attack_local(tree, t, stream, agent_index)
        if self.compression.active:
            v = jax.tree_util.tree_map(
                lambda l: l.astype(jnp.float32), sent)
            if ef is not None:
                v = jax.tree_util.tree_map(
                    lambda a, r: a - r, v, ef["ref"])
            c = jax.tree_util.tree_map(self.compressor.encode_decode, v)
            if self.compression.compress_after > 0:
                warm = self._require_t(t) < self.compression.compress_after
                c = jax.tree_util.tree_map(
                    lambda vv, cc: jnp.where(warm, vv, cc), v, c)
            if ef is None:
                ef_new, recon = None, c
            else:
                recon = jax.tree_util.tree_map(
                    lambda r, cc: r + cc, ef["ref"], c)
                ef_new = {"e": jax.tree_util.tree_map(
                              lambda a, b: a - b, v, c),
                          "ref": recon}
            payload = jax.tree_util.tree_map(
                lambda cc, l: cc.astype(l.dtype), recon, tree)
            mixed = permute_mix_tree(
                tree, self.agent_axes, self.schedule, compress=None,
                dp_sigma=self.dp_sigma if dp_key is not None else 0.0,
                dp_key=dp_key, impl=self.impl, agent_index=agent_index,
                payload_tree=payload, override=matrix)
            mixed = self._damp(mixed, tree)
        else:
            mixed = self.mix(sent, matrix=matrix, dp_key=dp_key,
                             agent_index=agent_index)
            ef_new = ef
        return self._apply_interval(t, mixed, tree, ef_new, ef)

    def bytes_on_wire(self, tree) -> int:
        """Per-leaf payloads × ppermute rounds (what each link carries).

        The legacy ``compress="int8"`` wire format is accounted with the
        int8 compressor when no ``CompressionConfig`` is active.
        """
        compressor = self.compressor
        if not self.compression.active and self.compress == "int8":
            compressor = Int8Compressor()
        per_leaf = sum(compressor.bytes_on_wire(int(l.size))
                       for l in jax.tree_util.tree_leaves(tree))
        return self.rounds_per_mix * per_leaf
