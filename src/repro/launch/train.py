"""End-to-end training driver: decentralized bilevel LM training.

Runs real INTERACT iterations (not a dry-run) on whatever devices exist —
the same code path scales from 1 CPU to the production mesh.  For CPU use,
pick a reduced config (``--reduced``) and give the host as many devices as
agents (``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

Example (the deliverable-scale run: ~100M-param model, few hundred steps):

  PYTHONPATH=src python -m repro.launch.train \
      --arch smollm-360m --reduced --steps 300 --agents 4 \
      --per-agent-batch 4 --seq-len 256 --ckpt-dir /tmp/interact_ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpoint import latest_step, restore_step, save_step
from repro.configs import ARCH_IDS, get_config
from repro.data.synthetic import TokenTaskStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import agent_axes, make_mesh, make_production_mesh
from repro.sharding.partition import tree_shardings
from repro.train.bilevel_lm import BilevelHyper
from repro.train.step import (
    InteractConfig, TrainState, init_train_state, make_train_step,
    train_state_specs)


def make_host_mesh(num_agents: int):
    """A ``(num_agents, model)`` mesh over the devices that exist.

    One agent per mesh row; the devices left over per agent form the
    ``model`` axis.  Refuses an agent count the devices cannot hold
    rather than training fewer agents than asked for.
    """
    devs = jax.devices()
    n = len(devs)
    m = int(num_agents)
    if m < 1 or m > n:
        hint = ""
        if devs[0].platform == "cpu":
            hint = (" — on CPU set XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count={max(m, 1)} before any jax import")
        raise ValueError(
            f"--agents {m} needs one {devs[0].platform} device per agent, "
            f"found {n}{hint}")
    model = n // m
    return make_mesh((m, model), ("data", "model"), devices=devs[:m * model])


@dataclasses.dataclass
class TrainRun:
    """What ``main`` returns: the final state and the logged steps."""

    state: TrainState
    log: list[dict]


def main(argv: Sequence[str] | None = None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--per-agent-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--alpha", type=float, default=0.02)
    ap.add_argument("--beta", type=float, default=0.5)
    ap.add_argument("--neumann-k", type=int, default=3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=1024, dtype="float32")

    if args.production_mesh:
        mesh = make_production_mesh()
    else:
        mesh = make_host_mesh(args.agents)
    a_axes = agent_axes(mesh)
    m = int(np.prod([mesh.shape[a] for a in a_axes]))
    aent = a_axes if len(a_axes) > 1 else a_axes[0]
    print(f"mesh {dict(mesh.shape)}; {m} agents; arch {cfg.name} "
          f"({'reduced' if args.reduced else 'full'})")

    icfg = InteractConfig(
        alpha=args.alpha, beta=args.beta,
        hyper=BilevelHyper(mu_g=0.1, neumann_k=args.neumann_k,
                           lipschitz_g=2.0,
                           ce_chunk=min(512, args.seq_len),
                           remat=not args.reduced))

    state = init_train_state(cfg, jax.random.PRNGKey(0), m)
    specs = train_state_specs(state, mesh)
    state = jax.device_put(state, tree_shardings(mesh, specs))

    start = 0
    if args.ckpt_dir:
        # latest_step validates (skips corrupt/truncated files); the
        # fallback covers a file rotting between the two calls — a
        # crashed run resumes from the newest checkpoint that actually
        # restores (docs/RESILIENCE.md).
        last = latest_step(args.ckpt_dir)
        if last is not None:
            print(f"restoring step {last} from {args.ckpt_dir}")
            state = jax.device_put(
                restore_step(args.ckpt_dir, last, state, fallback=True),
                tree_shardings(mesh, specs))
            start = last

    stream = TokenTaskStream(vocab_size=cfg.vocab_size, num_agents=m, seed=7)
    step_fn = make_train_step(cfg, mesh, icfg)
    tok_shard = NamedSharding(mesh, P(aent))

    log = []
    with jax.set_mesh(mesh):
        jstep = jax.jit(step_fn, donate_argnums=(0,))
        t0 = time.time()
        for t in range(start, args.steps):
            tokens = jax.device_put(
                stream.global_batch(t, args.per_agent_batch, args.seq_len),
                tok_shard)
            state, metrics = jstep(state, tokens)
            if (t + 1) % args.log_every == 0:
                ce = float(metrics["outer_ce"])
                gn = float(metrics["grad_norm"])
                dt = (time.time() - t0) / args.log_every
                log.append({"step": t + 1, "outer_ce": ce, "grad_norm": gn,
                            "s_per_step": dt})
                print(f"step {t + 1:5d}  outer_ce {ce:.4f}  "
                      f"tracked_grad_norm {gn:.3e}  {dt:.2f}s/step")
                t0 = time.time()
            if args.ckpt_dir and (t + 1) % args.ckpt_every == 0:
                save_step(args.ckpt_dir, t + 1, jax.device_get(state))
                print(f"checkpointed step {t + 1}")

    print("done.")
    return TrainRun(state=state, log=log)


if __name__ == "__main__":
    main()
