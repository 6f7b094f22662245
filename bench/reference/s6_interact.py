"""Plain reference: INTERACT (Algorithm 1) on the Section-6 meta-learning
problem, and the paper's eq.-11 convergence metric.

Written from the paper, in float32 ``jax.numpy``: every matrix product
at ``highest`` precision for the reference, or from three bfloat16
passes (``bf16x3``, the TPU's ``high``) for the control.  It imports
nothing of the program.

Per agent i, with backbone x (two tanh layers), head y = (W, b):

    g_i(x, y) = CE(head(features(x, inner split))) + mu/2 ||y||^2
    f_i(x, y) = CE(head(features(x, outer split)))
    p_i = grad_x f - H_xy(g) z,  z = CG(H_yy(g), grad_y f)   (eq. 5)
    v_i = grad_y g

Step 1  x <- M x - alpha u ;  y <- y - beta v
Step 2  p, v at the new iterate
Step 3  u <- M u + p - p_prev

CG runs the configuration's fixed trip count and freezes once the
residual norm is under its absolute tolerance, as the configuration
states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    and not a round trip through ``astype``: XLA may drop a float32 ->
    bfloat16 -> float32 pair of converts as excess precision."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def bf16x3_matmul(a, b):
    """A float32 product from three bfloat16 passes (hi*hi + hi*lo +
    lo*hi), as the TPU's ``high`` precision computes it; written out so
    that the control reads the same on any backend."""
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    mm = lambda u, v: jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST)
    return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _vdot(a, b):
    return sum(jnp.sum(x * y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                               jax.tree_util.tree_leaves(b)))


class Problem:
    """The meta-learning losses at one matmul precision."""

    def __init__(self, conf: dict, precision: str):
        if precision not in ("highest", "bf16x3"):
            raise ValueError(f"unknown precision {precision!r}")
        self.low = precision == "bf16x3"
        self.mu = float(conf["mu_g"])
        hg = conf["hypergrad"]
        self.cg_iters, self.cg_tol = int(hg["cg_iters"]), float(hg["cg_tol"])

    def dot(self, a, b):
        if self.low:
            return bf16x3_matmul(a, b)
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def logits(self, x, y, inputs):
        h = inputs
        for w, b in x:
            h = jnp.tanh(self.dot(h, w) + b)
        return self.dot(h, y[0]) + y[1]

    def ce(self, x, y, batch):
        inputs, labels = batch
        logp = jax.nn.log_softmax(self.logits(x, y, inputs), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    def outer(self, x, y, batch):
        return self.ce(x, y, batch)

    def inner(self, x, y, batch):
        w, b = y
        return self.ce(x, y, batch) + 0.5 * self.mu * (jnp.sum(w * w)
                                                      + jnp.sum(b * b))

    def cg(self, matvec, rhs):
        """Fixed trip count; the iterate freezes under the tolerance."""
        def body(_, c):
            z, r, d, rs = c
            ad = matvec(d)
            den = _vdot(d, ad)
            a = jnp.where(den > 0, rs / jnp.maximum(den, 1e-30), 0.0)
            live = jnp.sqrt(rs) > self.cg_tol
            a = jnp.where(live, a, 0.0)
            z = _tmap(lambda zz, dd: zz + a * dd, z, d)
            r = _tmap(lambda rr, aa: rr - a * aa, r, ad)
            rs_new = _vdot(r, r)
            bt = jnp.where(live, rs_new / jnp.maximum(rs, 1e-30), 0.0)
            d = _tmap(lambda rr, dd: rr + bt * dd, r, d)
            return z, r, d, jnp.where(live, rs_new, rs)

        z0 = _tmap(jnp.zeros_like, rhs)
        z, _, _, _ = jax.lax.fori_loop(0, self.cg_iters, body,
                                       (z0, rhs, rhs, _vdot(rhs, rhs)))
        return z

    def hypergrad(self, x, y, inner_b, outer_b):
        gx, gy = jax.grad(self.outer, argnums=(0, 1))(x, y, outer_b)
        grad_y = lambda xx, yy: jax.grad(self.inner, argnums=1)(xx, yy,
                                                                inner_b)
        hvp = lambda d: jax.jvp(lambda yy: grad_y(x, yy), (y,), (d,))[1]
        z = self.cg(hvp, gy)
        cross = jax.grad(lambda xx: _vdot(grad_y(xx, y), z))(x)
        return _tmap(jnp.subtract, gx, cross)

    def grads(self, x, y, inner_b, outer_b):
        p = self.hypergrad(x, y, inner_b, outer_b)
        v = jax.grad(self.inner, argnums=1)(x, y, inner_b)
        return p, v

    def mix(self, mat, tree):
        return _tmap(lambda l: self.dot(mat, l.reshape(l.shape[0], -1)
                                        ).reshape(l.shape), tree)


_PROBLEMS: dict = {}


def problem(conf: dict, precision: str) -> Problem:
    """One ``Problem`` per (configuration, precision): it is a static jit
    argument, so reusing it reuses the compiled programs."""
    key = (repr(sorted(conf.items())), precision)
    if key not in _PROBLEMS:
        _PROBLEMS[key] = Problem(conf, precision)
    return _PROBLEMS[key]


def _split(data):
    inner_x, inner_y, outer_x, outer_y = data
    return (inner_x, inner_y), (outer_x, outer_y)


@partial(jax.jit, static_argnums=(0,))
def init(prob: Problem, x0, y0, data):
    m = data[0].shape[0]
    bc = lambda t: _tmap(lambda l: jnp.broadcast_to(l, (m,) + l.shape), t)
    x, y = bc(x0), bc(y0)
    inner_b, outer_b = _split(data)
    p, v = jax.vmap(prob.grads)(x, y, inner_b, outer_b)
    return dict(x=x, y=y, u=p, v=v, p_prev=p)


@partial(jax.jit, static_argnums=(0, 5))
def run(prob: Problem, state, data, mat, ab, steps: int):
    """``steps`` INTERACT iterations; ``ab`` = (alpha, beta)."""
    alpha, beta = ab
    inner_b, outer_b = _split(data)

    def step(s, _):
        x = _tmap(lambda mx, u: mx - alpha * u, prob.mix(mat, s["x"]),
                  s["u"])
        y = _tmap(lambda yy, vv: yy - beta * vv, s["y"], s["v"])
        p, v = jax.vmap(prob.grads)(x, y, inner_b, outer_b)
        u = _tmap(lambda mu, pn, pp: mu + (pn - pp), prob.mix(mat, s["u"]),
                  p, s["p_prev"])
        return dict(x=x, y=y, u=u, v=v, p_prev=p), None

    out, _ = jax.lax.scan(step, state, xs=None, length=steps)
    return out


@partial(jax.jit, static_argnums=(0, 4))
def metric(prob: Problem, x, y, data, inner_steps: int, inner_lr):
    """Eq. 11: ||grad l(x_bar)||^2 + (1/m) sum ||x_i - x_bar||^2
    + sum ||y_i*(x_i) - y_i||^2, with y* by gradient descent."""
    inner_b, outer_b = _split(data)
    m = jax.tree_util.tree_leaves(x)[0].shape[0]
    x_bar = _tmap(lambda l: jnp.mean(l, axis=0), x)

    def solve_inner(xx, y0, batch):
        gy = jax.grad(prob.inner, argnums=1)
        return jax.lax.fori_loop(
            0, inner_steps,
            lambda _, yy: _tmap(lambda a, g: a - inner_lr * g, yy,
                                gy(xx, yy, batch)), y0)

    sq = lambda t: sum(jnp.sum(jnp.square(l))
                       for l in jax.tree_util.tree_leaves(t))
    cons = sum(jax.tree_util.tree_leaves(_tmap(
        lambda xi, xb: jnp.sum(jnp.square(xi - xb[None])), x, x_bar))) / m

    def inner_err(xi, yi, b):
        return sq(_tmap(jnp.subtract, solve_inner(xi, yi, b), yi))

    inner = jnp.sum(jax.vmap(inner_err)(x, y, inner_b))

    def at_bar(yi, ib, ob):
        ys = solve_inner(x_bar, yi, ib)
        return prob.hypergrad(x_bar, ys, ib, ob)

    p = jax.vmap(at_bar)(y, inner_b, outer_b)
    stat = sq(_tmap(lambda l: jnp.mean(l, axis=0), p))
    return stat + cons + inner


def solve(conf: dict, precision: str, data, x0, y0, mat, check_every: int,
          checks: int, stop_below: float | None = None):
    """Run blocks of ``check_every`` steps, reading the metric after each:
    ``checks`` blocks, or fewer when the metric falls to ``stop_below``.
    Returns the final (x, y) and the metric after each block."""
    prob = problem(conf, precision)
    mat = jnp.asarray(mat, jnp.float32)
    ab = (jnp.float32(conf["alpha"]), jnp.float32(conf["beta"]))
    met = conf["metric"]
    state = init(prob, x0, y0, data)
    trace = []
    for _ in range(checks):
        state = run(prob, state, data, mat, ab, check_every)
        trace.append(float(metric(prob, state["x"], state["y"], data,
                                  int(met["inner_steps"]),
                                  jnp.float32(met["inner_lr"]))))
        if stop_below is not None and trace[-1] <= stop_below:
            break
    return state["x"], state["y"], trace
