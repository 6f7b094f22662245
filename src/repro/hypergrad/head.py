"""Head-space hypergradient for problems whose y reads out features of x.

For ``f = outer(features(x), y)`` and ``g = inner(features(x), y)``
(``repro.core.bilevel.HeadSplit``) eq. (5) factors through the
features: H_yy(g) depends on x only through the inner split's features
F, so the K-term Neumann series of eq. (22) runs on F held fixed, and
the cross term is one backward through ``features``:

    F_o, F_i   = features(x, outer inputs), features(x, inner inputs)
    z          = (1/L) sum_{j<K} (I - H_yy/L)^j grad_y f      (head space)
    p          = J_x(F_o)^T d_F f  -  J_x(F_i)^T d_F <grad_y g, z>
    v          = grad_y g

Each split's features are computed once per call and shared by p, v and
the K head-space products; no Hessian-vector product re-runs the
backbone.  ``features`` treats the rows of its inputs independently, so
both splits (equal sequence lengths) go through the backbone as one
batch: one forward, and one backward with the cotangent (d_F f,
-d_F <grad_y g, z>) that yields p as a single gradient buffer.  ``jax.linearize`` of grad_y g at F_i gives v as its primal
output and the head-space HVP as its tangent map, and the chain skips
the discarded K-th product (K-1 HVPs, as ``neumann-linearized``).
Counts: K-1 head-space HVPs plus the H_xy cross term; two gradient
evaluations (the joint grad of f, the linearization's grad_y g).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing
from repro.hypergrad.config import HypergradConfig
from repro.hypergrad.neumann import neumann_truncated_apply
from repro.hypergrad.operator import HypergradStats, LinearOperator, flat_dot

__all__ = ["head_hypergradient_with_stats"]


def head_hypergradient_with_stats(split, x, y, cfg: HypergradConfig,
                                  f_batch, g_batch):
    """``(p, v, stats)`` of one agent: eq. (5) through ``split``'s
    features, grad_y g, and the counted evaluations.

    ``f_batch`` / ``g_batch`` are ``(inputs, labels)`` with inputs of
    equal shape past the batch axis.  The ``neumann-linearized`` backend
    calls this for a problem with a ``HeadSplit`` and a deterministic
    series (``cfg.stochastic_k`` false).
    """
    if cfg.stochastic_k:
        raise ValueError("the head-space hypergradient runs the "
                         "deterministic Neumann series")
    (f_in, f_lab), (g_in, g_lab) = f_batch, g_batch
    # both splits' rows in one pass through the backbone: one forward,
    # and one backward that yields grad_x f - H_xy z as a single buffer
    n_o = f_in.shape[0]
    feats, back = jax.vjp(
        lambda xx: split.features(xx, jnp.concatenate([f_in, g_in])), x)
    feats_o, feats_i = feats[:n_o], feats[n_o:]
    d_feats_o, gy_f = jax.grad(split.outer, argnums=(0, 1))(
        feats_o, y, f_lab)

    grad_y_g = lambda ff, yy: jax.grad(split.inner, argnums=1)(ff, yy,
                                                                g_lab)
    v, hvp_lin = jax.linearize(lambda yy: grad_y_g(feats_i, yy), y)
    with tracing.scope(tracing.HYPERGRAD):
        z, count = neumann_truncated_apply(
            LinearOperator(hvp_lin), gy_f, cfg.neumann_k, cfg.lipschitz_g,
            skip_last=True)
    d_feats_i = jax.grad(lambda ff: flat_dot(grad_y_g(ff, y), z))(feats_i)
    (p,) = back(jnp.concatenate([d_feats_o, -d_feats_i]).astype(
        feats.dtype))
    stats = HypergradStats(hvp_count=count + 1,             # + H_xy z
                           grad_count=jnp.int32(2),
                           hess_count=jnp.int32(0))
    return p, v, stats
