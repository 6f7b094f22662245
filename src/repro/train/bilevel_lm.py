"""The paper's bilevel problem instantiated on the assigned architectures.

Per agent i (Section 3.2 meta-learning form, scaled up):

  outer  f_i(x, y_i) = CE(head y_i on backbone_x(outer split)) + router aux
  inner  g_i(x, y_i) = CE(head y_i on backbone_x(inner split)) + (mu/2)||y_i||^2

x = backbone parameters (consensus variable), y_i = per-agent LM head
(d_model, vocab) — strongly convex inner problem via the ridge.

Hypergradient (eq. 5 / 22) exploits the readout structure: H_yy(g) touches
x only through the backbone features, so the K-term Neumann series runs in
*head space* on cached features (K cheap HVPs, no backbone recompute); the
single cross-term H_xy z is one extra backward through the backbone.  This
is mathematically identical to eq. (22) — the factorisation is recorded as
a beyond-paper efficiency in EXPERIMENTS.md §Perf.

The LM-head cross entropy is computed in *sequence chunks* (lax.scan) so
the (tokens, vocab) logits tensor never materialises — peak activation
memory drops from O(b s V / shards) to O(b chunk V / shards).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.hypergrad.neumann import neumann_truncated_apply
from repro.models import model as M
from repro.models.base import ArchConfig

__all__ = ["BilevelHyper", "chunked_ce", "inner_loss", "outer_loss",
           "local_grads", "ridge"]

DEFAULT_CE_CHUNK = 512


@dataclasses.dataclass(frozen=True)
class BilevelHyper:
    """Hyper-parameters of the bilevel LM problem + estimator."""

    mu_g: float = 0.1            # inner strong convexity (ridge)
    neumann_k: int = 4           # K of eq. (22)
    lipschitz_g: float = 2.0     # L_g scale for the Neumann series
    ce_chunk: int = DEFAULT_CE_CHUNK
    remat: bool = True
    attn_impl: str = "reference"
    seq_shard: bool = False   # P4: sequence-shard the residual stream
    batch_shard: bool = False  # P6: batch-shard residuals over 'data'
    microbatch: int = 1        # P8: gradient-accumulation microbatches


def ridge(y: jax.Array, mu: float) -> jax.Array:
    return 0.5 * mu * jnp.sum(jnp.square(y.astype(jnp.float32)))


def chunked_ce(cfg: ArchConfig, head: jax.Array, feats: jax.Array,
               labels: jax.Array, chunk: int) -> jax.Array:
    """Next-token CE with the head applied chunk-by-chunk over tokens.

    feats: (b, s, d) backbone outputs; labels: (b, s) token ids (the
    sequence itself — shift happens here).  The prefix (vlm/audio) part of
    feats, if any, is dropped by aligning on the label length.
    """
    b, s_lab = labels.shape
    n_pre = feats.shape[1] - s_lab
    f = feats[:, n_pre:][:, :-1]                     # predict next token
    l = labels[:, 1:]
    ft = f.reshape(-1, f.shape[-1])
    lt = l.reshape(-1)
    n = ft.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        ft = jnp.pad(ft, ((0, pad), (0, 0)))
        lt = jnp.pad(lt, ((0, pad),))
    valid = (jnp.arange(ft.shape[0]) < n).astype(jnp.float32)
    ft = ft.reshape(-1, chunk, ft.shape[-1])
    lt = lt.reshape(-1, chunk)
    vt = valid.reshape(-1, chunk)

    def body(acc, xs):
        fc, lc, vc = xs
        logits = M.head_logits(cfg, head, fc[None]).astype(jnp.float32)[0]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum((logz - gold) * vc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (ft, lt, vt))
    return total / jnp.float32(n)


def _backbone(cfg: ArchConfig, x, tokens, prefix, hyper: BilevelHyper):
    from jax.sharding import PartitionSpec as P
    act_spec = None
    if hyper.batch_shard:
        act_spec = P("data", None, None)
    elif hyper.seq_shard:
        act_spec = P(None, "model", None)
    return M.features(cfg, x, tokens, prefix_embed=prefix,
                      impl=hyper.attn_impl, remat=hyper.remat,
                      act_spec=act_spec)


def inner_loss(cfg: ArchConfig, hyper: BilevelHyper, x, y, tokens,
               prefix=None) -> jax.Array:
    feats, _aux = _backbone(cfg, x, tokens, prefix, hyper)
    return (chunked_ce(cfg, y, feats, tokens, hyper.ce_chunk)
            + ridge(y, hyper.mu_g))


def outer_loss(cfg: ArchConfig, hyper: BilevelHyper, x, y, tokens,
               prefix=None) -> jax.Array:
    feats, aux = _backbone(cfg, x, tokens, prefix, hyper)
    ce = chunked_ce(cfg, y, feats, tokens, hyper.ce_chunk)
    return ce + cfg.router_aux_weight * aux


def _head_loss_on_feats(cfg: ArchConfig, hyper: BilevelHyper, y, feats,
                        labels) -> jax.Array:
    return (chunked_ce(cfg, y, feats, labels, hyper.ce_chunk)
            + ridge(y, hyper.mu_g))


def _neumann_head(cfg, hyper: BilevelHyper, y, feats, labels, b):
    """[H_yy g]^{-1} b via the K-term Neumann series in head space.

    The head-space HVP is linearized once (``jax.linearize`` on the head
    gradient at the cached features) and the K-term chain of eq. (22)
    replays the stored tangent through the shared
    ``repro.hypergrad.neumann_truncated_apply`` — the engine package's
    linearize-once discipline applied to the LM fast path, with the
    chain's final (discarded) HVP skipped.
    """
    grad_fn = jax.grad(
        lambda yy: _head_loss_on_feats(cfg, hyper, yy, feats, labels))
    _, hvp_lin = jax.linearize(grad_fn, y)
    z, _count = neumann_truncated_apply(
        hvp_lin, b, hyper.neumann_k, hyper.lipschitz_g, skip_last=True)
    return z


def _accum_grads(loss_of_tokens, args, tokens, k, argnums):
    """Gradient accumulation over k microbatches (perf P8): peak
    activation memory of the pass drops by ~k; grads are exact means."""
    b = tokens.shape[0]
    tb = tokens.reshape(k, b // k, *tokens.shape[1:])

    def body(carry, toks):
        val, grads = carry
        v, g = jax.value_and_grad(loss_of_tokens, argnums=argnums)(
            *args, toks)
        grads = jax.tree_util.tree_map(
            lambda a, gi: a + gi / k, grads, g)
        return (val + v / k, grads), None

    zeros = jax.tree_util.tree_map(
        jnp.zeros_like, tuple(args[i] for i in argnums))
    (val, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), tb)
    return val, grads


def local_grads(cfg: ArchConfig, hyper: BilevelHyper, x, y,
                inner_tokens, outer_tokens, prefix_inner=None,
                prefix_outer=None):
    """(p, v, outer_ce): the paper's eqs. (8)-(9) for the LM problem.

    p = grad_x f - H_xy(g) [H_yy(g)]^{-1} grad_y f     (hypergradient)
    v = grad_y g                                        (inner gradient)
    """
    k = hyper.microbatch
    use_mb = (k > 1 and prefix_outer is None and prefix_inner is None
              and outer_tokens.shape[0] % k == 0
              and inner_tokens.shape[0] % k == 0)

    # --- outer: grad wrt both x and y (one fwd+bwd through the backbone).
    def f_loss(xp, yh):
        return outer_loss(cfg, hyper, xp, yh, outer_tokens, prefix_outer)

    if use_mb:
        outer_val, (gx_f, gy_f) = _accum_grads(
            lambda xp, yh, toks: outer_loss(cfg, hyper, xp, yh, toks),
            (x, y), outer_tokens, k, (0, 1))
    else:
        outer_val, (gx_f, gy_f) = jax.value_and_grad(
            f_loss, argnums=(0, 1))(x, y)

    # --- inner features, computed once and reused by the K head-space HVPs.
    feats_in, _ = _backbone(cfg, x, inner_tokens, prefix_inner, hyper)
    feats_in = jax.lax.stop_gradient(feats_in)
    z = _neumann_head(cfg, hyper, y, feats_in, inner_tokens, gy_f)

    # --- cross term H_xy(g) z = grad_x d/de g(x, y + e z)  (one fwd+bwd).
    if use_mb:
        def cross_mb(xp, toks):
            def g_of_y(yh):
                return inner_loss(cfg, hyper, xp, yh, toks, None)
            return jax.jvp(g_of_y, (y,), (z,))[1]

        _, (gx_cross,) = _accum_grads(cross_mb, (x,), inner_tokens, k, (0,))
    else:
        def cross(xp):
            def g_of_y(yh):
                return inner_loss(cfg, hyper, xp, yh, inner_tokens,
                                  prefix_inner)
            return jax.jvp(g_of_y, (y,), (z,))[1]

        gx_cross = jax.grad(cross)(x)

    p = jax.tree_util.tree_map(lambda a, b: a - b, gx_f, gx_cross)
    v = jax.grad(
        lambda yh: _head_loss_on_feats(cfg, hyper, yh, feats_in,
                                       inner_tokens))(y)
    return p, v, outer_val
