"""An ``ArchConfig`` language-model backbone as a ``BilevelProblem``.

Per agent i (the Section-3.2 meta-learning form at LM scale):

    x     the backbone (embedding rows, layers, final norm): consensus
    y_i   agent i's LM head (d_model, vocabulary held): private
    f_i   CE of head y_i on backbone_x features of the outer batch
    g_i   CE on the inner batch + (mu_g / 2) ||y_i||^2

A batch is ``(inputs, labels)``, both ``(batch, seq)`` token ids with
``labels`` the next token of each position.  The problem carries a
``HeadSplit``, so INTERACT's local gradients take the head-space path
of ``repro.hypergrad.head``: features once per split, K-1 head-space
HVPs, one backward for the cross term.  The CE runs in token chunks so
the (tokens, vocabulary) logits never exist whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.bilevel import BilevelProblem, HeadSplit
from repro.models import layers as L
from repro.models import model as M
from repro.models.base import ArchConfig

__all__ = ["LMBilevelProblem", "head_ce"]


@tracing.scoped(tracing.LM_HEAD)
def head_ce(head: jax.Array, feats: jax.Array, labels: jax.Array,
            chunk: int) -> jax.Array:
    """Mean CE of ``feats @ head`` against ``labels`` over all tokens,
    ``chunk`` tokens of logits at a time (float32 logits)."""
    d = feats.shape[-1]
    ft, lt = feats.reshape(-1, d), labels.reshape(-1)
    n = ft.shape[0]
    chunk = min(chunk, n)
    if n % chunk:
        raise ValueError(f"{n} tokens do not split into chunks of {chunk}")

    def body(acc, xs):
        fc, lc = xs
        logits = L.matmul(fc, head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (ft.reshape(-1, chunk, d), lt.reshape(-1, chunk)))
    return total / jnp.float32(n)


def LMBilevelProblem(cfg: ArchConfig, mu_g: float = 0.1,
                     lipschitz_g: float = 2.0, ce_chunk: int = 512,
                     attn_impl: str = "blockwise") -> BilevelProblem:
    """The bilevel LM problem on ``cfg``'s backbone (its held share)."""

    def features(x, inputs):
        feats, _aux = M.features(cfg, x, inputs, impl=attn_impl)
        return feats

    def outer_head(feats, y, labels):
        return head_ce(y, feats, labels, ce_chunk)

    def inner_head(feats, y, labels):
        ridge = 0.5 * mu_g * jnp.sum(jnp.square(y.astype(jnp.float32)))
        return head_ce(y, feats, labels, ce_chunk) + ridge

    def outer(x, y, batch):
        return outer_head(features(x, batch[0]), y, batch[1])

    def inner(x, y, batch):
        return inner_head(features(x, batch[0]), y, batch[1])

    return BilevelProblem(
        outer=outer, inner=inner, mu_g=mu_g, lipschitz_g=lipschitz_g,
        head=HeadSplit(features=features, outer=outer_head,
                       inner=inner_head))
