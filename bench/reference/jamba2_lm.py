"""Plain reference: one TP rank's share of AI21-Jamba2-3B as the outer
variable of INTERACT, with per-agent LM heads as the inner variable.

Straight ``jax.numpy`` in float32, every product under
``jax.default_matmul_precision("highest")``; no kernels, no shard_map,
no parallel-prefix or chunked scan: the SSM steps one position at a
time, the layers of a run go one after another (``lax.scan``), each
layer recomputed in the backward (``jax.checkpoint``) so it fits.  It
imports nothing of the program.  ``init_point`` makes the seeded initial
point, which is handed to the program and to ``interact`` alike, in the
program's layout: ``layers`` a list of the period's runs of equal
layers, each leaf with a leading period axis of 1 and a run axis.

Initial point from the key: every projection normal / sqrt(fan-in)
(embedding rows and head over d_model, the attention output over the
held heads' width, x_proj and out_proj over the held channels, dt_proj
over dt_rank, FFN down over the held columns); conv taps normal x 0.2,
conv bias 0; A S4D-real (A_n = -n), dt bias -4, D 1; every RMSNorm
weight 1 (scale 0).

The share (HF Jamba, ``modeling_jamba.py``; arXiv 2403.19887):

    h  = embed[inputs]                                   (unscaled)
    per layer:  h += mixer(RMSNorm(h));  h += FFN(RMSNorm(h))
    mixer, attention (one layer of the period): q, k, v projections,
        causal softmax(q k^T / sqrt(128)) v per query head, the one kv
        head shared by the held query heads (MQA), no positional
        encoding, o projection
    mixer, Mamba: (u, z) = in_proj(a); u = silu(causal_conv(u) + bias);
        (r, B, C) = x_proj(u); r, B, C each RMSNorm'd; dt = softplus(
        dt_proj(r) + dt_bias); h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t
        one position at a time; y_t = C_t . h_t + D u_t;
        out_proj(y * silu(z))
    FFN: down(silu(gate(a)) * up(a))
    features = RMSNorm(h)

Departures from HF Jamba, shared with the program: RMSNorm weights are
stored as ``1 + scale``; the embedding is not tied to the head (the head
is the inner variable y); the layer is one rank's share of a tensor-
parallel group, and the absent ranks' partial sums of x_proj, out_proj,
o_proj and the FFN down projection are left out; the vocabulary is the
held slice.

Bilevel problem per agent i, batch = (inputs, labels of the next token):

    f_i(x, y) = CE(features(x, outer inputs) @ y, outer labels)
    g_i(x, y) = CE(features(x, inner inputs) @ y, inner labels)
                + (mu / 2) ||y||^2
    H v       = F^T (P o (F v) - P o rowsum(P o (F v))) / n + mu v
    z         = (1/L) sum_{j<K} (I - H/L)^j grad_y f          (eq. 22)
    p_i       = grad_x f - grad_x <grad_y g(x, y), z>          (eq. 5)
    v_i       = grad_y g = F^T (P - Y) / n + mu y

INTERACT (Algorithm 1) over the agents, vmapped, the agent axis laid
across the devices:

    x_i <- sum_j M_ij x_j - alpha u_i ;  y_i <- y_i - beta v_i
    (p_i, v_i) at the new iterate
    u_i <- sum_j M_ij u_j + p_i - p_i^prev
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
TOKEN_BLOCK = 2048


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def rms_norm(p, x, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * (1.0 + p["scale"]))


def silu(x):
    return x * jax.nn.sigmoid(x)


def attention(p, a):
    """Causal softmax attention, one held query head after another."""
    b, s, _ = a.shape
    hd = p["wq"].shape[-1]
    k = jnp.einsum("bsd,dk->bsk", a, p["wk"][:, 0])
    v = jnp.einsum("bsd,dk->bsk", a, p["wv"][:, 0])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(out, w):
        wq, wo = w
        q = jnp.einsum("bsd,dk->bsk", a, wq)
        logits = jnp.einsum("bqk,bsk->bqs", q, k) / jnp.sqrt(float(hd))
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
        return out + jnp.einsum("bqs,bsk,kd->bqd", probs, v, wo), None

    out, _ = jax.lax.scan(head, jnp.zeros_like(a),
                          (jnp.moveaxis(p["wq"], 1, 0), p["wo"]))
    return out


def mamba(p, a, eps):
    """The selective SSM, one position after another."""
    d_state = p["b_norm"]["scale"].shape[0]
    xz = a @ p["w_in"]
    u, z = jnp.split(xz, 2, axis=-1)
    d_conv = p["conv_w"].shape[0]
    padded = jnp.pad(u, ((0, 0), (d_conv - 1, 0), (0, 0)))
    u = silu(sum(padded[:, j:j + u.shape[1]] * p["conv_w"][j]
                 for j in range(d_conv)) + p["conv_b"])
    xp = u @ p["w_x"]
    rank = xp.shape[-1] - 2 * d_state
    r = rms_norm(p["dt_norm"], xp[..., :rank], eps)
    B = rms_norm(p["b_norm"], xp[..., rank:rank + d_state], eps)
    C = rms_norm(p["c_norm"], xp[..., rank + d_state:], eps)
    dt = jax.nn.softplus(r @ p["w_dt"] + p["dt_bias"])
    A = -jnp.exp(p["a_log"])

    def one(hs, ts):
        dt_t, u_t, b_t, c_t = ts
        hs = (jnp.exp(dt_t[..., None] * A) * hs
              + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return hs, jnp.einsum("bdn,bn->bd", hs, c_t)

    h0 = jnp.zeros((a.shape[0], u.shape[-1], d_state), jnp.float32)
    seq = lambda t: jnp.moveaxis(t, 1, 0)
    _, ys = jax.lax.scan(one, h0, (seq(dt), seq(u), seq(B), seq(C)),
                         unroll=1)
    y = jnp.moveaxis(ys, 0, 1) + p["d_skip"] * u
    return (y * silu(z)) @ p["w_out"]


def ffn(p, a):
    return (silu(a @ p["w_gate"]) * (a @ p["w_up"])) @ p["w_down"]


def features(conf, x, inputs):
    """The share's final-normed hidden states, (batch, seq, d_model)."""
    eps = conf["rms_norm_eps"]
    h = x["embed"][inputs].astype(jnp.float32)

    def block(p, h):
        a = rms_norm(p["pre_norm"], h, eps)
        h = h + (attention(p["attn"], a) if "attn" in p
                 else mamba(p["mamba"], a, eps))
        return h + ffn(p["mlp"], rms_norm(p["post_norm"], h, eps))

    layer = jax.checkpoint(block)
    for run in x["layers"]:
        p = _tmap(lambda l: l[0], run)      # the one period
        h, _ = jax.lax.scan(lambda hh, pp: (layer(pp, hh), None), h, p)
    return rms_norm(x["final_norm"], h, eps)


def runs(conf):
    """The held period's runs of equal layers, ``[(mixer, length)]``:
    attention where i % attn_layer_period == attn_layer_offset."""
    period = conf["attn_layer_period"]
    if conf["num_hidden_layers"] != period:
        raise ValueError("the reference holds one period")
    out = []
    for i in range(period):
        mixer = "attn" if i == conf["attn_layer_offset"] else "mamba"
        if out and out[-1][0] == mixer:
            out[-1][1] += 1
        else:
            out.append([mixer, 1])
    return [tuple(r) for r in out]


def init_point(conf, key):
    """The seeded initial point ``(x0, y0)``: the share's backbone and one
    head, float32, by the scheme of the module's docstring."""
    d, held = conf["hidden_size"], conf["held"]
    heads, kv, hd = held["query_heads"], held["kv_heads"], conf["head_dim"]
    d_in, d_ff, vocab = (held["mamba_channels"], held["ffn_columns"],
                         held["vocab_rows"])
    n, conv, rank = (conf["mamba_d_state"], conf["mamba_d_conv"],
                     conf["mamba_dt_rank"])
    count = iter(range(1 << 20))

    def normal(shape, scale):
        k = jax.random.fold_in(key, next(count))
        return jax.random.normal(k, shape, jnp.float32) * scale

    fan = lambda f: 1.0 / np.sqrt(f)
    norm = lambda lead, w: {"scale": jnp.zeros(lead + (w,), jnp.float32)}
    full = lambda lead, shape, v: jnp.full(lead + shape, v, jnp.float32)

    def layers(mixer, length):
        lead = (1, length)
        p = dict(pre_norm=norm(lead, d), post_norm=norm(lead, d),
                 mlp=dict(w_gate=normal(lead + (d, d_ff), fan(d)),
                          w_up=normal(lead + (d, d_ff), fan(d)),
                          w_down=normal(lead + (d_ff, d), fan(d_ff))))
        if mixer == "attn":
            p["attn"] = dict(wq=normal(lead + (d, heads, hd), fan(d)),
                             wk=normal(lead + (d, kv, hd), fan(d)),
                             wv=normal(lead + (d, kv, hd), fan(d)),
                             wo=normal(lead + (heads, hd, d),
                                       fan(heads * hd)))
        else:
            a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
            p["mamba"] = dict(
                w_in=normal(lead + (d, 2 * d_in), fan(d)),
                conv_w=normal(lead + (conv, d_in), 0.2),
                conv_b=full(lead, (d_in,), 0.0),
                w_x=normal(lead + (d_in, rank + 2 * n), fan(d_in)),
                dt_norm=norm(lead, rank), b_norm=norm(lead, n),
                c_norm=norm(lead, n),
                w_dt=normal(lead + (rank, d_in), fan(rank)),
                dt_bias=full(lead, (d_in,), -4.0),
                a_log=jnp.broadcast_to(a, lead + (d_in, n)),
                d_skip=full(lead, (d_in,), 1.0),
                w_out=normal(lead + (d_in, d), fan(d_in)))
        return p

    x0 = dict(embed=normal((vocab, d), fan(d)), final_norm=norm((), d),
              layers=[layers(*r) for r in runs(conf)])
    return x0, normal((d, vocab), fan(d))


def _over_blocks(fn, init, feats, labels):
    """``fn(acc, feats_block, labels_block)`` over blocks of
    ``TOKEN_BLOCK`` tokens, one after another; returns (acc, n)."""
    f = feats.reshape(-1, feats.shape[-1])
    lab = labels.reshape(-1)
    n = f.shape[0]
    step = min(TOKEN_BLOCK, n)
    acc, _ = jax.lax.scan(
        lambda a, xs: (fn(a, *xs), None), init,
        (f.reshape(-1, step, f.shape[-1]), lab.reshape(-1, step)))
    return acc, n


def ce(feats, y, labels):
    def block(total, fb, lb):
        logits = fb @ y
        return total + jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                               - jnp.take_along_axis(
                                   logits, lb[:, None], axis=-1)[:, 0])
    total, n = _over_blocks(block, jnp.zeros((), jnp.float32), feats, labels)
    return total / n


def grad_y_g(feats, y, labels, mu):
    """F^T (P - Y) / n + mu y, block by block."""
    def block(acc, fb, lb):
        resid = (jax.nn.softmax(fb @ y, axis=-1)
                 - jax.nn.one_hot(lb, y.shape[1], dtype=jnp.float32))
        return acc + fb.T @ resid
    acc, n = _over_blocks(block, jnp.zeros_like(y), feats, labels)
    return acc / n + mu * y


def hess_yy(feats, y, mu, vec):
    """H_yy(g) vec = F^T (P o (F vec) - P o rowsum(P o (F vec))) / n
    + mu vec."""
    def block(acc, fb, _):
        p = jax.nn.softmax(fb @ y, axis=-1)
        w = p * (fb @ vec)
        return acc + fb.T @ (w - p * jnp.sum(w, axis=-1, keepdims=True))
    acc, n = _over_blocks(block, jnp.zeros_like(vec), feats,
                          jnp.zeros(feats.shape[:-1], jnp.int32))
    return acc / n + mu * vec


def local_gradients(conf, hyper, x, y, inner, outer):
    """(p, v, f): eq. (5) with the K-term series of eq. (22), grad_y g
    and the outer loss, all at (x, y)."""
    with jax.default_matmul_precision(HIGHEST):
        mu, lip, k = hyper["mu_g"], hyper["lipschitz_g"], hyper["neumann_k"]
        f_val, (gx_f, gy_f) = jax.value_and_grad(
            lambda xx, yy: ce(features(conf, xx, outer[0]), yy, outer[1]),
            argnums=(0, 1))(x, y)
        feats = jax.lax.stop_gradient(features(conf, x, inner[0]))
        term, acc = gy_f, gy_f
        for _ in range(k - 1):
            term = term - hess_yy(feats, y, mu, term) / lip
            acc = acc + term
        z = acc / lip
        cross = jax.grad(lambda xx: jnp.sum(grad_y_g(
            features(conf, xx, inner[0]), y, inner[1], mu) * z))(x)
        p = _tmap(lambda a, b: a - b, gx_f, cross)
        return p, grad_y_g(feats, y, inner[1], mu), f_val


def interact(conf, hyper, x0, y0, batches, mixing, alpha, beta, steps,
             devices):
    """``steps`` INTERACT iterations from (x0, y0) for every agent.

    The agents are vmapped, their axis laid across ``devices`` (jit
    partitions it: with as many devices as agents, each holds one
    agent), so one compiled program serves them all; the mix, the local
    gradients and the tracking update are programs of their own, so a
    phase's temporaries are gone before the next.  ``batches(k) ->
    (inner, outer)``: every agent's batch pair at step k, agent axis
    first (k = 0 also initialises u = p, v).  Returns ``x``, ``y``,
    ``u`` after the last step and ``f``, the outer loss at that iterate
    on the last step's batch, agent axis first.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mixing = np.asarray(mixing, np.float64)
    m = mixing.shape[0]
    mesh = Mesh(np.array(devices), ("agents",))
    agents = NamedSharding(mesh, P("agents"))
    grads = jax.jit(jax.vmap(lambda x, y, inner, outer: local_gradients(
        conf, hyper, x, y, inner, outer)), out_shardings=agents)
    rows = np.arange(m)

    def mix(tree):
        """sum_j M_ij t_j, term by term: t_{(i+o) mod m} = roll(t, -o)."""
        def leaf(l):
            out = 0.0
            for o in range(m):
                w = mixing[rows, (rows + o) % m]
                if np.any(w):
                    w = jnp.asarray(w, l.dtype).reshape((m,) + (1,) * (
                        l.ndim - 1))
                    out = out + w * jnp.roll(l, -o, axis=0)
            return out
        return _tmap(leaf, tree)

    descend = jax.jit(lambda x, u: _tmap(lambda mx, uu: mx - alpha * uu,
                                         mix(x), u),
                      donate_argnums=0, out_shardings=agents)
    mix_u = jax.jit(mix, donate_argnums=0, out_shardings=agents)
    track = jax.jit(lambda mu, pn, pp: _tmap(lambda a, b, c: a + b - c,
                                             mu, pn, pp),
                    donate_argnums=(0, 2), out_shardings=agents)
    spread = jax.jit(lambda t: _tmap(
        lambda l: jnp.broadcast_to(l, (m,) + l.shape), t),
        out_shardings=agents)

    put = lambda t: jax.device_put(t, agents)
    x, y = spread(x0), spread(y0)
    p, v, f = grads(x, y, *put(batches(0)))
    u = _tmap(jnp.copy, p)
    for k in range(steps):
        x = descend(x, u)
        y = y - beta * v
        u = mix_u(u)
        p_new, v, f = grads(x, y, *put(batches(k)))
        u = track(u, p_new, p)
        p = p_new
    return dict(x=x, y=y, u=u, f=f)
