"""Pallas consensus backend: the fused consensus+tracking kernel.

Wraps ``repro/kernels/consensus_step`` behind the ``ConsensusEngine`` API,
putting the kernel on the single-host m-agent simulator's hot loop: both
Step-1/3 matmuls run in one launch with the (m, m) mixing matrix
VMEM-resident and the flattened parameters streaming through once.
Arbitrary pytrees are handled by ``ravel_pytree`` and D is zero-padded to
the tile size inside the kernel, so any model / any dense ``M`` works.
The kernel runs compiled on TPU and interpreted on CPU
(``repro.kernels.resolve_interpret``); ``interpret=`` overrides that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.consensus.compress import CompressionConfig
from repro.consensus.engine import ConsensusEngine
from repro.core.consensus import MixingSpec
from repro.kernels.consensus_step.ops import consensus_mix, consensus_step

__all__ = ["PallasEngine"]


class PallasEngine(ConsensusEngine):

    name = "pallas"

    def __init__(self, mixing: MixingSpec | jax.Array,
                 interpret: bool | None = None,
                 compression: CompressionConfig | None = None,
                 communication_interval: int = 1, byzantine=None):
        mat = mixing.matrix if isinstance(mixing, MixingSpec) else mixing
        self.matrix = jnp.asarray(mat, jnp.float32)
        self.interpret = interpret
        self._configure_wire(compression, communication_interval, byzantine)

    def mix(self, tree, *, matrix=None, dp_key=None, agent_index=None):
        del dp_key, agent_index  # single-host backend: no wire, no DP
        mat = self.matrix if matrix is None else jnp.asarray(matrix,
                                                             jnp.float32)
        return consensus_mix(mat, tree, interpret=self.interpret)

    def step1_step3(self, x, u, p, p_prev, alpha, *, t=None, ef=None,
                    matrix=None, dp_key=None, agent_index=None):
        if ef is not None or self.wire_active:
            # wire path: compose two compressed mixes through the base
            # implementation (each still a kernel launch via self.mix);
            # the fused Step-1/3 kernel stays on the full-precision path.
            return super().step1_step3(x, u, p, p_prev, alpha, t=t, ef=ef,
                                       matrix=matrix, dp_key=dp_key,
                                       agent_index=agent_index)
        try:
            alpha_c = float(alpha)
        except (TypeError, jax.errors.ConcretizationTypeError):
            # traced step size (a sweep batch axis): the fused kernel
            # bakes alpha in at trace time, so compose the per-mix
            # kernel launches through the base implementation instead
            return super().step1_step3(x, u, p, p_prev, alpha, t=t,
                                       matrix=matrix, dp_key=dp_key,
                                       agent_index=agent_index)
        del dp_key, agent_index
        if matrix is None:
            matrix = self.topology_matrix(t, x)
        mat = self.matrix if matrix is None else jnp.asarray(matrix,
                                                             jnp.float32)
        return consensus_step(mat, x, u, p, p_prev,
                              alpha=alpha_c, interpret=self.interpret)
