"""The Section-6 meta-learning instances (arXiv 2207.13283, Section 6).

A copy of the program's generator (``repro.core.bilevel.
make_synthetic_agents`` / ``init_mlp_backbone`` / ``init_head`` and the
Erdős–Rényi Laplacian mixing of ``repro.core.consensus``), kept here so
that the benchmark's traffic and its reference cannot move with the
program.  Same arithmetic, same values for the same key.

An instance is ``(data, x0, y0)``: per-agent classification data split
into inner (train) and outer (validation) parts, the shared MLP backbone
and the linear head every agent starts from.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed, also those past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def agents_data(key, num_agents: int, n_per_agent: int, d_in: int,
                num_classes: int, heterogeneity: float, outer_frac: float):
    """``(inner_x, inner_y, outer_x, outer_y)``, leading axis the agents."""
    k_means, k_shift, k_lab, k_x = jax.random.split(key, 4)
    means = 2.0 * jax.random.normal(k_means, (num_classes, d_in))
    shifts = heterogeneity * jax.random.normal(k_shift,
                                               (num_agents, 1, d_in))
    conc = jnp.full((num_classes,), 1.0 / max(heterogeneity, 1e-3))
    probs = jax.random.dirichlet(k_lab, conc, shape=(num_agents,))
    labels = jax.vmap(
        lambda k, p: jax.random.categorical(k, jnp.log(p),
                                            shape=(n_per_agent,))
    )(jax.random.split(k_lab, num_agents), probs)
    noise = jax.random.normal(k_x, (num_agents, n_per_agent, d_in))
    xs = means[labels] + shifts + 0.75 * noise
    n_out = int(outer_frac * n_per_agent)
    return (xs[:, n_out:], labels[:, n_out:], xs[:, :n_out],
            labels[:, :n_out])


def backbone(key, d_in: int, hidden: int, depth: int, scale: float = 0.5):
    params = []
    dims = [d_in] + [hidden] * depth
    for i in range(depth):
        key, k1 = jax.random.split(key)
        w = scale * jax.random.normal(k1, (dims[i], dims[i + 1])) / np.sqrt(
            dims[i])
        params.append((w, jnp.zeros((dims[i + 1],))))
    return params


def head(key, hidden: int, num_classes: int, scale: float = 0.1):
    w = scale * jax.random.normal(key, (hidden, num_classes)) / np.sqrt(
        hidden)
    return (w, jnp.zeros((num_classes,)))


def instance(key, num_agents: int, conf: dict):
    """One instance ``(data, x0, y0)`` from ``key``; ``conf`` is the
    configuration file's sizes."""
    k_data, k_x, k_y = jax.random.split(key, 3)
    data = agents_data(k_data, num_agents, conf["n_per_agent"],
                       conf["d_in"], conf["classes"], conf["heterogeneity"],
                       conf["outer_frac"])
    x0 = backbone(k_x, conf["d_in"], conf["hidden"], conf["depth"])
    y0 = head(k_y, conf["hidden"], conf["classes"])
    return data, x0, y0


def erdos_renyi_mixing(m: int, p_connect: float, seed: int) -> np.ndarray:
    """The paper's W = I - 2L / (3 lambda_max(L)) on a connected ER graph
    (resampled until connected, ring edges added after 512 tries)."""
    rng = np.random.default_rng(seed)

    def connected(adj):
        seen, frontier = {0}, [0]
        while frontier:
            i = frontier.pop()
            for j in np.nonzero(adj[i])[0]:
                if int(j) not in seen:
                    seen.add(int(j))
                    frontier.append(int(j))
        return len(seen) == m

    for _ in range(512):
        adj = np.triu(rng.random((m, m)) < p_connect, k=1)
        adj = (adj | adj.T).astype(np.float64)
        if connected(adj):
            break
    else:
        adj = np.triu(rng.random((m, m)) < p_connect, k=1)
        adj = (adj | adj.T).astype(np.float64)
        for i in range(m):
            adj[i, (i + 1) % m] = adj[(i + 1) % m, i] = 1.0
        np.fill_diagonal(adj, 0.0)
    lap = np.diag(adj.sum(axis=1)) - adj
    lam_max = float(np.linalg.eigvalsh(lap)[-1])
    return np.eye(m) - 2.0 * lap / (3.0 * lam_max)
