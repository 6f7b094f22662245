"""Robust combine rules: replacements for the ``M @ X`` contraction.

A :class:`CombineRule` aggregates, for each agent i, the payload rows of
its in-neighborhood — the support ``{j : M[i, j] != 0} ∪ {i}`` of its
mixing row.  Restricting to the support keeps two properties the rest
of the repo depends on:

* **Topology-respecting**: an agent only ever reads payloads its links
  actually deliver, so robust rules compose with link-failure streams
  and gossip matrices unchanged.
* **Ghost-pad invariance**: padded mixing matrices give ghost slots an
  identity row and zero cross-weights, so a ghost is in nobody's
  support (and its own support is just itself).  Whatever garbage a
  ghost row carries, active agents' aggregates are bitwise those of the
  unpadded run — the property ``sweep(..., pad_agents=True)`` is priced
  against.

Unlike ``weighted``, the robust rules are *nonlinear* in the payload:
they are not doubly-stochastic contractions (no exact average
preservation) and the engine's self-clean error-feedback correction
does not apply (see docs/BYZANTINE.md for the full matrix).  They need
all-to-all access to the payload rows, which only the dense backend
has; ``PermuteEngine`` refuses them loudly at construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "CombineRule",
    "combine_rule_names",
    "make_combine_rule",
    "register_combine_rule",
    "robust_combine",
]

_RULES: dict[str, type] = {}

_SUPPORT_TOL = 1e-12


def register_combine_rule(name: str):
    """Class decorator: register a :class:`CombineRule` under ``name``."""

    def wrap(cls):
        if name in _RULES:
            raise ValueError(f"combine rule {name!r} already registered "
                             f"({_RULES[name].__name__})")
        cls.name = name
        _RULES[name] = cls
        return cls

    return wrap


def combine_rule_names() -> tuple[str, ...]:
    return tuple(sorted(_RULES))


def make_combine_rule(name: str) -> "CombineRule":
    try:
        return _RULES[name]()
    except KeyError:
        raise ValueError(
            f"unknown combine rule {name!r}; registered: "
            f"{combine_rule_names()}") from None


class CombineRule:
    """Aggregate an (m, D) payload buffer row-neighborhood-wise.

    Attributes:
      needs_all_rows: True when the rule reads payload rows beyond the
        plain weighted contraction — i.e. it cannot run on a backend
        without the full (m, D) buffer (ppermute).
    """

    name = "?"
    needs_all_rows = True

    def aggregate(self, vals: jax.Array, support: jax.Array,
                  matrix: jax.Array, trim: int) -> jax.Array:
        """(m, D) float32 aggregate from (m, D) vals, (m, m) support."""
        raise NotImplementedError


@register_combine_rule("weighted")
class WeightedRule(CombineRule):
    """The paper's contraction ``M @ X`` — the bitwise no-op baseline.

    The only linear rule: preserves double stochasticity (exact average
    invariance) and the engine's self-clean property.  Zero Byzantine
    tolerance — one corrupted row moves every neighbor.
    """

    needs_all_rows = False

    def aggregate(self, vals, support, matrix, trim):
        del support, trim
        # HIGHEST: bf16-rounded weights break the exact average
        # (repro.core.consensus.mix_pytree)
        return jnp.matmul(matrix, vals, precision=jax.lax.Precision.HIGHEST)


@register_combine_rule("coordinate-median")
class CoordinateMedianRule(CombineRule):
    """Per-coordinate median over the in-neighborhood (incl. self).

    Breakdown point 1/2 of the neighborhood; ignores mixing weights
    (every support entry counts once).
    """

    def aggregate(self, vals, support, matrix, trim):
        del matrix, trim

        def one(sup_row):
            masked = jnp.where(sup_row[:, None], vals, jnp.nan)
            return jnp.nanmedian(masked, axis=0)

        return jax.vmap(one)(support)


@register_combine_rule("trimmed-mean")
class TrimmedMeanRule(CombineRule):
    """Drop the f smallest and f largest per coordinate, mean the rest.

    ``trim`` is f.  Tolerates f Byzantine in-neighbors per agent and
    needs ``2f < |support|``; a neighborhood too small to trim falls
    back to the plain support mean (never an empty aggregate).  The
    breakdown bound against the global m is enforced at engine
    construction (a loud config error, not a silent NaN).
    """

    def aggregate(self, vals, support, matrix, trim):
        del matrix
        m = vals.shape[0]
        idx = jnp.arange(m)[:, None]

        def one(sup_row):
            keyed = jnp.where(sup_row[:, None], vals, jnp.inf)
            order = jnp.argsort(keyed, axis=0)
            svals = jnp.take_along_axis(vals, order, axis=0)
            ssup = jnp.take_along_axis(
                jnp.broadcast_to(sup_row[:, None], vals.shape), order,
                axis=0)
            cnt = jnp.sum(sup_row)
            keep = ssup & (idx >= trim) & (idx < cnt - trim)
            keep = jnp.where(cnt > 2 * trim, keep, ssup)
            total = jnp.sum(jnp.where(keep, svals, 0.0), axis=0)
            return total / jnp.maximum(jnp.sum(keep, axis=0), 1)

        return jax.vmap(one)(support)


@register_combine_rule("krum-like")
class KrumLikeRule(CombineRule):
    """Nearest-neighbor screening: adopt the most central support row.

    Each agent scores every in-neighbor payload by its summed squared
    distance to the *other* support rows and adopts the row with the
    smallest score — a Krum-style selection restricted to the local
    neighborhood (true Krum also trims the k furthest from the score;
    with the small per-agent neighborhoods here the plain argmin is the
    stable variant).  Output is always one of the received rows, so a
    colluding majority in a neighborhood defeats it (breakdown at
    f >= |support|/2, like the other rules).
    """

    def aggregate(self, vals, support, matrix, trim):
        del matrix, trim
        diff = vals[:, None, :] - vals[None, :, :]
        d2 = jnp.sum(diff * diff, axis=-1)

        def one(sup_row):
            pair = sup_row[:, None] & sup_row[None, :]
            scores = jnp.sum(jnp.where(pair, d2, 0.0), axis=1)
            scores = jnp.where(sup_row, scores, jnp.inf)
            return vals[jnp.argmin(scores)]

        return jax.vmap(one)(support)


def robust_combine(matrix: jax.Array, tree, rule: str, trim: int = 1):
    """Aggregate a payload pytree under ``rule`` over the support of
    ``matrix`` (plus the diagonal), preserving leaf shapes/dtypes.

    Leaves are flattened to one (m, D) float32 buffer (krum scores need
    the full rows) and split back after aggregation.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    m = leaves[0].shape[0]
    flat = [leaf.astype(jnp.float32).reshape(m, -1) for leaf in leaves]
    sizes = [f.shape[1] for f in flat]
    vals = flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=1)
    mat = jnp.asarray(matrix, jnp.float32)
    support = (jnp.abs(mat) > _SUPPORT_TOL) | jnp.eye(m, dtype=bool)
    out = make_combine_rule(rule).aggregate(vals, support, mat, trim)
    pieces, off = [], 0
    for leaf, size in zip(leaves, sizes):
        piece = out[:, off:off + size]
        pieces.append(piece.reshape(leaf.shape).astype(leaf.dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, pieces)
