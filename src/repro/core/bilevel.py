"""Bilevel problem abstraction + the paper's meta-learning instance.

A ``BilevelProblem`` packages the per-agent outer loss f_i(x, y; batch) and
inner loss g_i(x, y; batch).  Problem (1) of the paper:

    min_x (1/m) sum_i f_i(x_i, y_i*(x_i)),
    y_i*(x_i) = argmin_y g_i(x_i, y_i),   g_i mu_g-strongly convex in y.

The reference instance is the Section-6 meta-learning task: a shared
two-hidden-layer backbone x (20 hidden units) and per-agent linear heads
y_i, with g_i = CE(train split) + (mu/2)||y||^2 so the inner problem is
strongly convex, and f_i = CE(validation split) — nonconvex in x.
MNIST/CIFAR are unavailable offline; a synthetic heterogeneous Gaussian
cluster generator stands in (see DESIGN.md §1).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "AgentData",
    "BilevelProblem",
    "MLPMetaProblem",
    "make_synthetic_agents",
    "init_mlp_backbone",
    "init_head",
    "pad_agent_data",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AgentData:
    """Per-agent dataset of n samples split into inner (train) / outer (val)."""

    inner_x: jax.Array  # (n_in, d)
    inner_y: jax.Array  # (n_in,) int labels
    outer_x: jax.Array  # (n_out, d)
    outer_y: jax.Array  # (n_out,)


@dataclasses.dataclass(frozen=True)
class BilevelProblem:
    """f(x, y, batch) outer loss, g(x, y, batch) inner loss.

    batch is an arbitrary pytree; for full-gradient algorithms pass the
    whole agent dataset, for stochastic ones pass a minibatch.

    ``inner_hess_yy`` is an optional closed form for the flat inner
    Hessian: ``inner_hess_yy(x, y, batch) -> (d_y, d_y)`` in
    ``ravel_pytree(y)`` ordering, ridge included.  The ``cholesky``
    hypergradient backend uses it instead of materialising H_yy through
    d_y automatic-differentiation HVPs (see docs/HYPERGRAD.md); every
    other backend ignores it, so it is purely an opt-in fast path.
    """

    outer: Callable  # f(x, y, (inputs, labels)) -> scalar
    inner: Callable  # g(x, y, (inputs, labels)) -> scalar
    mu_g: float      # strong-convexity modulus of g in y
    lipschitz_g: float  # gradient-Lipschitz bound L_g for the Neumann scale
    inner_hess_yy: Callable | None = None  # optional closed-form flat H_yy


def pad_agent_data(data: AgentData, pad_to: int) -> AgentData:
    """Ghost-pad the agent axis to ``pad_to`` by tiling real agents' data.

    Ghost agent i >= m sees a copy of agent ``i % m``'s dataset — real,
    finite samples, so the (discarded) ghost computations in a padded
    sweep group stay well-conditioned; zeros or NaN sentinels could leak
    through ``0 * NaN`` in the dense mixing matmul or blow up the ghost
    inner solves.  Active agents' rows are untouched (``i % m == i``).
    """
    m = data.inner_x.shape[0]
    if pad_to < m:
        raise ValueError(f"cannot pad {m} agents down to {pad_to}")
    if pad_to == m:
        return data
    idx = jnp.arange(pad_to) % m
    return jax.tree_util.tree_map(lambda leaf: leaf[idx], data)


# ---------------------------------------------------------------------------
# The paper's Section-6 instance: 2-hidden-layer MLP meta-learning.
# ---------------------------------------------------------------------------

def _dot(a, b):
    """f32 matmul at f32 precision.  The TPU's default rounds operands to
    bf16; the gradient differences SVR-INTERACT accumulates then drift
    (on one v5e chip: 2.2e-4 stationarity after 300 steps against 1.7e-4
    on CPU, with the consensus mix already exact)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _mlp_features(params, inputs):
    h = inputs
    for w, b in params:
        h = jnp.tanh(_dot(h, w) + b)
    return h


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def MLPMetaProblem(mu_g: float = 0.1, lipschitz_g: float = 4.0) -> BilevelProblem:
    """Backbone x = list[(W, b)], head y = (W_head, b_head).

    g(x, y) = CE(head(features(x, inner_x)), inner_y) + mu/2 ||y||^2
    f(x, y) = CE(head(features(x, outer_x)), outer_y)

    The inner problem is a linear head under softmax CE + ridge, so its
    Hessian wrt y has the closed form

        H[(i,c),(j,d)] = (1/n) sum_s phi_si phi_sj A_s[c,d] + mu I,
        A_s = diag(p_s) - p_s p_s^T,   phi_s = [features_s, 1],

    which ``inner_hess_yy`` materialises with two batched contractions —
    the ``cholesky`` hypergradient backend's small-head fast path.
    """

    def outer(x, y, batch):
        inputs, labels = batch
        feats = _mlp_features(x, inputs)
        w, b = y
        return _cross_entropy(_dot(feats, w) + b, labels)

    def inner(x, y, batch):
        inputs, labels = batch
        feats = _mlp_features(x, inputs)
        w, b = y
        ce = _cross_entropy(_dot(feats, w) + b, labels)
        reg = 0.5 * mu_g * (jnp.sum(w * w) + jnp.sum(b * b))
        return ce + reg

    def inner_hess_yy(x, y, batch):
        inputs, _labels = batch
        feats = _mlp_features(x, inputs)
        w, b = y
        p = jax.nn.softmax(_dot(feats, w) + b, axis=-1)   # (n, C)
        n, C = p.shape
        # phi rows [features, 1]: index i*C+c matches ravel_pytree((w, b))
        # = [w.ravel(), b] with the bias as the trailing phi column.
        phi = jnp.concatenate([feats, jnp.ones((n, 1), feats.dtype)],
                              axis=1)                      # (n, hd+1)
        hd1 = phi.shape[1]
        d = hd1 * C
        # A_s = diag(p_s) - p_s p_s^T split into its two contractions:
        # rank-one part as a gram of R[s,(i,c)] = phi_si p_sc, diagonal
        # part as C feature grams weighted by p[:, c].
        R = (phi[:, :, None] * p[:, None, :]).reshape(n, d)
        G = jnp.einsum('sc,si,sj->cij', p, phi, phi,
                       precision=jax.lax.Precision.HIGHEST)  # (C, hd+1, hd+1)
        H = -_dot(R.T, R)
        H = H.reshape(hd1, C, hd1, C)
        # diagonal (c == d) blocks via a broadcast against eye — a scatter
        # here lowers poorly under vmap on CPU
        H = H + (G.transpose(1, 0, 2)[:, :, :, None]
                 * jnp.eye(C)[None, :, None, :])
        return H.reshape(d, d) / n + mu_g * jnp.eye(d)

    return BilevelProblem(outer=outer, inner=inner, mu_g=mu_g,
                          lipschitz_g=lipschitz_g,
                          inner_hess_yy=inner_hess_yy)


def init_mlp_backbone(key: jax.Array, d_in: int, hidden: int = 20,
                      depth: int = 2, scale: float = 0.5):
    params = []
    dims = [d_in] + [hidden] * depth
    for i in range(depth):
        key, k1 = jax.random.split(key)
        w = scale * jax.random.normal(k1, (dims[i], dims[i + 1])) / np.sqrt(dims[i])
        params.append((w, jnp.zeros((dims[i + 1],))))
    return params


def init_head(key: jax.Array, hidden: int, num_classes: int,
              scale: float = 0.1):
    w = scale * jax.random.normal(key, (hidden, num_classes)) / np.sqrt(hidden)
    return (w, jnp.zeros((num_classes,)))


def make_synthetic_agents(
    key: jax.Array,
    num_agents: int,
    n_per_agent: int = 1000,
    d_in: int = 32,
    num_classes: int = 10,
    heterogeneity: float = 0.5,
    outer_frac: float = 0.3,
) -> AgentData:
    """Synthetic heterogeneous classification tasks (MNIST stand-in).

    Class means are shared globally; each agent sees a skewed label
    distribution (Dirichlet with concentration 1/heterogeneity) plus an
    agent-specific mean shift, giving genuinely different f_i / g_i per
    agent as in multi-agent meta-learning.

    Returns stacked AgentData with a leading agent axis.
    """
    k_means, k_shift, k_lab, k_x = jax.random.split(key, 4)
    means = 2.0 * jax.random.normal(k_means, (num_classes, d_in))
    shifts = heterogeneity * jax.random.normal(k_shift, (num_agents, 1, d_in))

    conc = jnp.full((num_classes,), 1.0 / max(heterogeneity, 1e-3))
    probs = jax.random.dirichlet(k_lab, conc, shape=(num_agents,))
    labels = jax.vmap(
        lambda k, p: jax.random.categorical(k, jnp.log(p), shape=(n_per_agent,))
    )(jax.random.split(k_lab, num_agents), probs)

    noise = jax.random.normal(k_x, (num_agents, n_per_agent, d_in))
    xs = means[labels] + shifts + 0.75 * noise

    n_out = int(outer_frac * n_per_agent)
    return AgentData(
        inner_x=xs[:, n_out:], inner_y=labels[:, n_out:],
        outer_x=xs[:, :n_out], outer_y=labels[:, :n_out],
    )
