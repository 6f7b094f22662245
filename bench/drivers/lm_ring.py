"""INTERACT on an LM backbone, one agent per chip, gossiping on a ring.

The cell's step is the registry INTERACT step (``SolverConfig`` /
``make_solver``, ``ppermute`` backend) on ``repro.core.lm_problem.LMBilevelProblem``,
run by ``repro.launch.distributed.MeshRunner``: each chip holds one
agent's float32 state (x, y, u, v, p_prev) and a pool of ``pool`` batch
pairs drawn from ``--seed``, cycled by the step counter, so the host
feeds nothing in the window.  Steps run back to back; the host waits on
a step only once the next one is queued, and reads nothing back.  The
window ends on a step boundary: ``agent_steps_per_s`` is agents x whole
steps over their own elapsed time.

Traffic: packed sequences of token ids drawn i.i.d. from a Zipf law
(exponent ``zipf_exponent``) over the vocabulary slice; an inner and an
outer batch of ``batch`` x ``seq_len`` tokens per agent step.

The initial point comes from the plain reference's ``init_point``
(``bench/reference/jamba2_lm.py``), made from ``--seed`` on every chip;
the program starts from it, in the window and in the check alike.

``correct``: after the window the program's own compiled init and step
run ``check_steps`` steps from that point on the pool's first pairs (the
window's first steps), and the plain reference does the same from the
same point.  Compared are the
updates x_N - x_0, y_N - y_0 and u_N, per agent and leaf, as
||prog - ref|| / max(||ref||, median leaf norm of that agent's tree),
and the outer loss at step N, relative.  An update below the resolution
of a lower-precision state vanishes and reads about 1.

``CONTROLS`` are the program with one lower-precision path or one part
of the mathematics switched (state in bfloat16, products from float8
e4m3 inputs, the mix left out, the attention layer left out); ``FAULTS``
are planted faults.  Each comes out as not correct
(``bench/tests/test_bench_jamba2_cell.py``; on the chip
``bench/lm_control.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from reference import jamba2_lm as reference

span = jax.profiler.TraceAnnotation


def setup(conf: dict, params: dict, seed: int, devices):
    return LMRing(conf, params, seed, devices)


def arch_config(conf: dict):
    """The held share as an ``ArchConfig``: the published preset with the
    configuration's depth, tensor-parallel share and scan chunk."""
    from repro.configs import get_config
    tp = int(conf["tensor_parallel"])
    cfg = dataclasses.replace(
        get_config(conf["preset"]),
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        d_ff=conf["intermediate_size"],
        num_heads=conf["num_attention_heads"] * tp,
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab_size=conf["vocab_size"] * tp,
        attn_every=conf["attn_layer_period"],
        attn_offset=conf["attn_layer_offset"],
        mamba_d_state=conf["mamba_d_state"], mamba_d_conv=conf["mamba_d_conv"],
        mamba_expand=conf["mamba_expand"],
        mamba_dt_rank=conf["mamba_dt_rank"], norm_eps=conf["rms_norm_eps"],
        mamba_seq_chunk=conf["mamba_seq_chunk"], tp_size=tp,
        dtype="float32")
    cfg.validate()
    held = conf["held"]
    assert (cfg.held_heads, cfg.held_kv_heads, cfg.held_d_inner,
            cfg.held_d_ff, cfg.held_vocab) == (
        held["query_heads"], held["kv_heads"], held["mamba_channels"],
        held["ffn_columns"], held["vocab_rows"]), held
    return cfg


def seed_rng(seed: int):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])


def make_pool(cfg, params: dict, seed: int) -> np.ndarray:
    """``(m, pool, 2, batch, seq_len + 1)`` ids: per agent and pair an
    inner and an outer batch of packed Zipf-distributed sequences."""
    vocab = cfg.held_vocab
    prob = 1.0 / np.arange(1, vocab + 1) ** float(params["zipf_exponent"])
    shape = (int(params["num_agents"]), int(params["pool"]), 2,
             int(params["batch"]), int(params["seq_len"]) + 1)
    ids = seed_rng(seed).choice(vocab, size=shape, p=prob / prob.sum())
    return ids.astype(np.int32)


def mixing_matrix(params: dict) -> np.ndarray:
    from repro.core.consensus import ring_mixing
    return ring_mixing(int(params["num_agents"]),
                       self_weight=float(params["self_weight"])).matrix


def norm(a) -> float:
    """Euclidean norm of a float32 array (BLAS nrm2, scaled: accurate)."""
    return float(np.linalg.norm(np.asarray(a, np.float32).ravel()))


def leaf_gaps(prog, ref) -> float:
    """Worst leaf of ||prog - ref|| / max(||ref leaf||, median leaf)."""
    p_leaves = jax.tree_util.tree_leaves(prog)
    r_leaves = jax.tree_util.tree_leaves(ref)
    norms = [norm(r) for r in r_leaves]
    floor = float(np.median(norms))
    return max(norm(np.asarray(p, np.float32) - np.asarray(r, np.float32))
               / max(n, floor, 1e-30)
               for p, r, n in zip(p_leaves, r_leaves, norms))


def compare(prog: list, ref: list, limits: dict) -> list:
    """The four compared numbers, worst over agents."""
    gaps = dict(dx_gap=0.0, dy_gap=0.0, u_gap=0.0, loss_gap=0.0)
    for p, r in zip(prog, ref):
        gaps["dx_gap"] = max(gaps["dx_gap"], leaf_gaps(p["dx"], r["dx"]))
        gaps["dy_gap"] = max(gaps["dy_gap"], leaf_gaps(p["dy"], r["dy"]))
        gaps["u_gap"] = max(gaps["u_gap"], leaf_gaps(p["u"], r["u"]))
        gaps["loss_gap"] = max(gaps["loss_gap"],
                               abs(p["f"] - r["f"]) / abs(r["f"]))
    return [dict(name=k, value=v, limit=limits[k]) for k, v in gaps.items()]


class LMRing:

    def __init__(self, conf: dict, params: dict, seed: int, devices):
        from repro.consensus import attach_ledger
        from repro.core.bilevel import AgentData
        from repro.core.lm_problem import LMBilevelProblem
        from repro.hypergrad import HypergradConfig
        from repro.launch.distributed import MeshRunner
        from repro.launch.mesh import make_mesh
        from repro.solvers import SolverConfig, TopologyConfig, make_solver
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.conf, self.params, self.seed = conf, params, seed
        self.devices = list(devices)
        m = self.m = int(params["num_agents"])
        if len(self.devices) != m:
            raise SystemExit(f"lm_ring: one agent per device, {m} agents "
                             f"on {len(self.devices)} devices")
        cfg = self.cfg = arch_config(conf)
        hyper = conf["bilevel"]
        self.hg = HypergradConfig(backend="neumann-linearized",
                                  neumann_k=int(hyper["neumann_k"]),
                                  lipschitz_g=float(hyper["lipschitz_g"]))
        self.problem = LMBilevelProblem(
            cfg, mu_g=float(hyper["mu_g"]),
            lipschitz_g=float(hyper["lipschitz_g"]),
            ce_chunk=int(conf["ce_chunk"]), attn_impl=conf["attn_impl"])
        solver = make_solver(SolverConfig(
            algo=params["algo"], alpha=float(hyper["alpha"]),
            beta=float(hyper["beta"]), num_agents=m,
            backend=params["backend"],
            backend_opts={"agent_axes": ("data",)}, hypergrad=self.hg,
            topology=TopologyConfig(kind=params["topology"],
                                    self_weight=float(params["self_weight"]))))
        seq = int(params["seq_len"])
        solver.build(self.problem, self.hg, m=m, n=2 * seq)
        self.ledger = attach_ledger(solver._engine)

        ids = self.ids = make_pool(cfg, params, seed)
        pool = AgentData(inner_x=ids[:, :, 0, :, :-1],
                         inner_y=ids[:, :, 0, :, 1:],
                         outer_x=ids[:, :, 1, :, :-1],
                         outer_y=ids[:, :, 1, :, 1:])
        n_pool = int(params["pool"])

        def batch_of(d, t):
            pick = lambda l: jax.lax.dynamic_index_in_dim(
                l, t % n_pool, axis=1, keepdims=False)
            return jax.tree_util.tree_map(pick, d)

        mesh = make_mesh(shape=(m,), axis_names=("data",),
                         devices=self.devices)
        self.runner = MeshRunner(solver, m, batch_of=batch_of, mesh=mesh)
        self.pool = self.runner.put(pool)
        key = jax.random.fold_in(
            jax.random.PRNGKey(int(seed) & 0xFFFFFFFF),
            (int(seed) >> 32) & 0xFFFFFFFF)
        # the reference's seeded initial point, made on every chip
        self.point = functools.partial(jax.jit(
            lambda k: reference.init_point(conf, k),
            out_shardings=NamedSharding(mesh, P())), key)
        state_dtype = jnp.dtype(params.get("state_dtype", "float32"))
        init = self.runner.init_fn(jax.eval_shape(self.point), self.pool)
        self.init = lambda point, pool: init(jax.tree_util.tree_map(
            lambda l: l.astype(state_dtype), point), pool)
        outer = self.problem.outer

        def loss(s, d):
            b = batch_of(d, s.t - 1)
            return jax.vmap(lambda x, y, bx, by: outer(x, y, (bx, by)))(
                s.x, s.y, b.outer_x, b.outer_y)

        self.loss = jax.jit(self.runner.smap(
            loss, (self.runner.agent_specs(jax.eval_shape(
                self.init, jax.eval_shape(self.point), self.pool)),
                self.runner.agent_specs(self.pool)), P("data")))
        # warm every program of the window and the check: the point, init,
        # one step, the outer loss; then start the window from a fresh init
        with jax.set_mesh(mesh):
            st, _ = self.runner.run(self.init(self.point(), self.pool),
                                    self.pool, 1)
            self.loss(st, self.pool).block_until_ready()
            del st
            self.state = self.init(self.point(), self.pool)
            jax.block_until_ready(self.state.t)
        self.mesh = mesh

    def window(self, seconds: float) -> dict:
        steps, pending = 0, None
        with jax.set_mesh(self.mesh):
            t0 = time.perf_counter()
            while True:
                with span("bench.lm.step"):
                    self.state, done = self.runner.run(self.state, self.pool,
                                                       1)
                steps += 1
                if pending is not None:
                    with span("bench.lm.wait"):
                        pending.block_until_ready()
                pending = done
                if time.perf_counter() - t0 >= seconds:
                    break
            pending.block_until_ready()
            elapsed = time.perf_counter() - t0
        finite = bool(jnp.isfinite(jnp.sum(self.state.y)))
        return dict(metrics={"agent_steps_per_s": self.m * steps / elapsed},
                    attempted=self.m * steps,
                    failed=0 if finite else self.m * steps,
                    elapsed_s=elapsed, agent_steps=self.m * steps)

    def counters(self) -> dict:
        """HVPs per agent step as the program counts them
        (``repro.hypergrad.local_gradients`` on a cut of this cell's
        problem, same backend, K and code path: the count depends on
        neither widths nor values), and the CommsLedger's bytes per
        step."""
        return {"hvp_per_step": hvp_count(self.conf, self.hg,
                                          self.devices[0]),
                "wire_bytes_per_step": self.ledger.bytes_per_step()}

    def release(self) -> None:
        self.state = None

    def program_updates(self) -> list:
        """The program's ``check_steps`` steps from the seeded initial
        point: per agent the updates, u_N and f_N, on the host."""
        n = int(self.params["check_steps"])
        host = lambda t: jax.tree_util.tree_map(
            lambda l: np.asarray(jax.device_get(l), np.float32), t)
        with jax.set_mesh(self.mesh):
            point = self.point()
            self.x0, self.y0 = host(point)
            st = self.init(point, self.pool)
            del point
            for _ in range(n):
                st, _ = self.runner.run(st, self.pool, 1)
            f = np.asarray(self.loss(st, self.pool), np.float64)
            x, y, u = host((st.x, st.y, st.u))
        del st
        return [dict(dx=jax.tree_util.tree_map(lambda a, b: a[i] - b, x,
                                               self.x0),
                     dy=y[i] - self.y0,
                     u=jax.tree_util.tree_map(lambda a: a[i], u),
                     f=float(f[i])) for i in range(self.m)]

    def reference_updates(self) -> list:
        """The plain reference from the same seeded initial point."""
        x0, y0 = self.x0, self.y0
        hyper = self.conf["bilevel"]
        ids = self.ids

        def batches(k):
            pair = ids[:, k % ids.shape[1]]
            return ((pair[:, 0, :, :-1], pair[:, 0, :, 1:]),
                    (pair[:, 1, :, :-1], pair[:, 1, :, 1:]))

        out = jax.device_get(reference.interact(
            self.conf, hyper, x0, y0, batches, mixing_matrix(self.params),
            float(hyper["alpha"]), float(hyper["beta"]),
            int(self.params["check_steps"]), self.devices))
        return [dict(dx=jax.tree_util.tree_map(lambda a, b: a[i] - b,
                                               out["x"], x0),
                     dy=out["y"][i] - y0,
                     u=jax.tree_util.tree_map(lambda a: a[i], out["u"]),
                     f=float(out["f"][i])) for i in range(self.m)]

    def check(self) -> list:
        prog = self.program_updates()
        return compare(prog, self.reference_updates(),
                       self.params["limits"])


def hvp_count(conf: dict, hg, device) -> float:
    from repro.core.lm_problem import LMBilevelProblem
    from repro.hypergrad import local_gradients
    cut = dict(conf, hidden_size=32, intermediate_size=64,
               num_attention_heads=1, head_dim=8, vocab_size=16,
               mamba_d_state=4, mamba_dt_rank=4, mamba_seq_chunk=0,
               held=dict(query_heads=1, kv_heads=1, mamba_channels=16,
                         ffn_columns=16, vocab_rows=16))
    cfg = arch_config(cut)
    prob = LMBilevelProblem(cfg, mu_g=0.1, lipschitz_g=hg.lipschitz_g,
                            ce_chunk=8, attn_impl=conf["attn_impl"])
    toks = jnp.zeros((1, 8), jnp.int32)
    with jax.default_device(device):
        x, y = reference.init_point(cut, jax.random.PRNGKey(0))
        _, _, stats = jax.jit(lambda x, y: local_gradients(
            prob, hg, x, y, (toks, toks), (toks, toks)))(x, y)
        return float(stats.hvp_count)


def _round_inputs(fn, exponent_bits: int, mantissa_bits: int):
    """``fn`` with each product's inputs rounded to the given format."""
    r = lambda a: jax.lax.reduce_precision(a, exponent_bits=exponent_bits,
                                           mantissa_bits=mantissa_bits)

    def rounded(*args):
        *head, a, b = args
        return fn(*head, r(a), r(b))

    return rounded


def _controls():
    from repro.consensus.dense import DenseEngine
    from repro.consensus.ppermute import PermuteEngine
    from repro.models import layers as L
    no_attn = lambda params, x, positions, **kw: (jnp.zeros_like(x), None)
    return {
        "state_bf16": [],
        "matmul_f8": [(L, "matmul", _round_inputs(L.matmul, 4, 3)),
                      (L, "einsum", _round_inputs(L.einsum, 4, 3))],
        # not a control: the chip's default products (bf16 inputs, f32
        # accumulation) on a CPU, for reading the sound program's gaps
        "products_bf16": [(L, "matmul", _round_inputs(L.matmul, 8, 7)),
                          (L, "einsum", _round_inputs(L.einsum, 8, 7))],
        "no_mix": [(PermuteEngine, "mix", lambda self, tree, **kw: tree),
                   (DenseEngine, "mix", lambda self, tree, **kw: tree)],
        "no_attention": [(L, "attention", no_attn)],
    }


def _faults():
    import repro.core.interact as interact
    from repro.launch.distributed import MeshRunner

    def half(data):
        inner, outer = orig_batch(data)
        cut = lambda b: tuple(a[..., :a.shape[-1] // 2] for a in b)
        return cut(inner), cut(outer)

    def altered(self):
        return [dict(p, f=p["f"] * 1.02) for p in orig_updates(self)]

    orig_batch = interact._per_agent_batch
    orig_updates = LMRing.program_updates
    return {
        "stale_state": [(MeshRunner, "run", lambda self, state, data, n:
                         (state, jnp.copy(state.t)))],
        "half_batch": [(interact, "_per_agent_batch", half)],
        "altered_answer": [(LMRing, "program_updates", altered)],
    }


CONTROLS = ("state_bf16", "matmul_f8", "no_mix", "no_attention")
FAULTS = ("stale_state", "half_batch", "altered_answer")


def control_params(params: dict, name: str) -> dict:
    """The traffic's parameters for a control run (``state_bf16`` is a
    parameter; the others are patches, ``planted``)."""
    if name == "state_bf16":
        return dict(params, state_dtype="bfloat16")
    return params


@contextlib.contextmanager
def planted(name: str):
    table = dict(_controls(), **_faults())
    patches = table[name]
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    try:
        for obj, attr, new in patches:
            setattr(obj, attr, new)
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
