"""all-gather consensus backend: the dense combine on the device mesh.

The open cell of the backend matrix (ROADMAP item 1): the dense matmul
reference, runnable *inside* ``shard_map`` across processes.  Each agent
``lax.all_gather``\\ s every peer's payload along the agent axes and dots
its own rows of the full (m, m) mixing matrix against the gathered
table:

    mixed[i] = M[i, :] @ gathered          (eq. 6 / eq. 10 left term)

Trade-off vs ppermute: the wire carries one payload per agent per round
(the broadcast model ``cumulative_wire_bytes`` prices — so measured
bytes match the priced model exactly, the property the
``check_distributed`` gate asserts), while ppermute ships one payload
per *link* per permute round (cheaper on sparse graphs with few
offsets, pricier on dense ones).  Because the engine holds the full
matrix, arbitrary **traced** matrix overrides work — time-varying
topology streams run on the mesh without a permute-weight schedule —
and the Byzantine robust rules (which need all-to-all payload access)
run here exactly as on the dense backend.

Must be called from inside a shard_map body whose manual axes include
``agent_axes``; leaves carry the local agent's slice (leading local
dim).  Local-DP noise is a ppermute wire option and is ignored here,
like on the single-host backends.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.byzantine import robust_combine
from repro.consensus.compress import CompressionConfig
from repro.consensus.engine import ConsensusEngine, MeshBackendMixin
from repro.core.consensus import MixingSpec

__all__ = ["AllGatherEngine"]


class AllGatherEngine(MeshBackendMixin, ConsensusEngine):

    name = "allgather"

    def __init__(self, mixing: MixingSpec | jax.Array,
                 agent_axes: Sequence[str] = ("data",),
                 compression: CompressionConfig | None = None,
                 communication_interval: int = 1, byzantine=None):
        mat = mixing.matrix if isinstance(mixing, MixingSpec) else mixing
        self.matrix = jnp.asarray(mat)
        self.agent_axes = tuple(agent_axes)
        self._slots_hint = None
        self._configure_wire(compression, communication_interval, byzantine)

    @property
    def _mesh_num_agents(self) -> int:
        return int(self.matrix.shape[0])

    def _gather(self, tree):
        """Gather every agent's rows along the agent axes: leaves
        (rows, ...) -> (m * rows, ...), ordered like ``_local_slots``
        (minor axis gathered first, so the final order is major-to-minor
        over ``agent_axes``)."""
        def leaf(l):
            out = l
            for ax in reversed(self.agent_axes):
                out = jax.lax.all_gather(out, ax, axis=0, tiled=True)
            return out
        return jax.tree_util.tree_map(leaf, tree)

    def mix(self, tree, *, matrix=None, dp_key=None, agent_index=None):
        del dp_key  # DP noise is a ppermute wire option; ignored here
        mat = self.matrix if matrix is None else matrix
        slots = self._local_slots(tree, agent_index)
        rows = jnp.asarray(mat, jnp.float32)[slots]
        gathered = self._gather(tree)

        def combine(g, l):
            # HIGHEST: see repro.core.consensus.mix_pytree
            mixed = jnp.tensordot(rows, g.astype(jnp.float32),
                                  axes=[[1], [0]],
                                  precision=jax.lax.Precision.HIGHEST)
            return mixed.astype(l.dtype)

        return jax.tree_util.tree_map(combine, gathered, tree)

    def _self_weights(self, matrix=None) -> jax.Array:
        """Self weights M[i, i] of the *local* rows.

        The base wire path broadcasts these against the local leaves, so
        under shard_map they must be the slot slice of the diagonal —
        ``mix_ef`` installs the slots before delegating to the base
        implementation."""
        mat = self.matrix if matrix is None else matrix
        diag = jnp.diagonal(jnp.asarray(mat, jnp.float32))
        return diag if self._slots_hint is None else diag[self._slots_hint]

    def _combine(self, tree, *, matrix=None, dp_key=None, agent_index=None):
        """Weighted mix, or a robust rule over the gathered rows.

        Unlike ppermute (which never holds more than the local slice),
        the gathered table gives every agent all-to-all access, so the
        Byzantine robust rules run here exactly as on the dense backend:
        each agent computes the full robust combine and keeps its rows.
        """
        rule = self.byzantine.combine
        if rule == "weighted":
            return self.mix(tree, matrix=matrix, dp_key=dp_key,
                            agent_index=agent_index)
        mat = self.matrix if matrix is None else matrix
        slots = self._local_slots(tree, agent_index)
        gathered = self._gather(tree)
        full = robust_combine(jnp.asarray(mat, jnp.float32), gathered, rule,
                              self.byzantine.resolve_trim())
        return jax.tree_util.tree_map(
            lambda fl, l: fl[slots].astype(l.dtype), full, tree)

    def _attack_payload(self, tree, t, stream):
        # local-slice corruption with global slot identities (bitwise vs
        # the dense reference, like ppermute)
        return self._attack_local(tree, t, stream, None)

    def mix_ef(self, tree, ef=None, t=None, *, matrix=None, dp_key=None,
               agent_index=None, stream="x"):
        """Base wire path with the self-clean weights sliced per slot.

        The compression/EF math is the base implementation verbatim —
        one concatenated per-agent buffer, byte-identical accounting to
        the dense backend; only the self-weight broadcast needs the
        local slot slice (see ``_self_weights``).
        """
        if not self.compression.active:
            return super().mix_ef(tree, ef, t, matrix=matrix,
                                  dp_key=dp_key, agent_index=agent_index,
                                  stream=stream)
        self._slots_hint = self._local_slots(tree, agent_index)
        try:
            return super().mix_ef(tree, ef, t, matrix=matrix,
                                  dp_key=dp_key, agent_index=agent_index,
                                  stream=stream)
        finally:
            self._slots_hint = None
