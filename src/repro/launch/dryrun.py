"""Multi-pod dry-run: lower + compile every (architecture x input shape).

For each combination this driver builds the production mesh, constructs
ShapeDtypeStruct inputs (no allocation), lowers the appropriate step
(train_step for train_4k, prefill/serve for the inference shapes),
compiles it, and records:

  * compiled.memory_analysis()  — proves the program fits per-device HBM
  * compiled.cost_analysis()    — HLO FLOPs / bytes for the roofline
  * collective statistics parsed from the optimized HLO — wire bytes per
    collective kind for the roofline's communication term.

Results are written as JSON under experiments/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
from __future__ import annotations

# The dry-run needs 512 placeholder devices; jax locks the device count at
# first init, so this MUST precede every jax import (including repro.*).
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import pathlib
import re
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch import input_specs as specs_mod
from repro.launch.mesh import agent_axes, make_production_mesh
from repro.launch.serving import make_prefill_step, make_serve_step
from repro.roofline.analysis import normalize_cost_analysis
from repro.models.base import ArchConfig
from repro.sharding.partition import (
    cache_specs, leaf_spec, tree_shardings, tree_specs)
from repro.train.bilevel_lm import BilevelHyper
from repro.train.step import (
    InteractConfig, make_train_step, train_state_specs)

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

COLLECTIVE_RE = re.compile(
    r"=\s*(?P<dtype>[a-z0-9]+)\[(?P<shape>[0-9,]*)\][^ ]*\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

# Wire-byte weights per collective (ring algorithms, per participating
# chip): all-reduce moves ~2x the tensor, the others ~1x.
_WIRE_WEIGHT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def parse_collectives(hlo_text: str) -> dict[str, Any]:
    stats: dict[str, dict[str, float]] = {}
    for mt in COLLECTIVE_RE.finditer(hlo_text):
        op = mt.group("op")
        shape = mt.group("shape")
        numel = 1
        if shape:
            for d in shape.split(","):
                if d:
                    numel *= int(d)
        nbytes = numel * _DTYPE_BYTES.get(mt.group("dtype"), 4)
        ent = stats.setdefault(op, {"count": 0, "bytes": 0.0})
        ent["count"] += 1
        ent["bytes"] += nbytes
    total_wire = sum(_WIRE_WEIGHT[k] * v["bytes"] for k, v in stats.items())
    return {"per_op": stats, "wire_bytes": total_wire}


OPT_MOE_CHUNK = 8192


def optimized_config(cfg: ArchConfig) -> ArchConfig:
    """Beyond-paper perf variant (EXPERIMENTS.md §Perf): chunked MoE
    dispatch (P3), expert-parallel pinning when E % 16 == 0 (P5);
    blockwise attention (P2) and selective sequence sharding (P4) are
    threaded via attn_impl / seq_shard below."""
    import dataclasses
    if cfg.num_experts:
        cfg = dataclasses.replace(
            cfg, moe_token_chunk=OPT_MOE_CHUNK,
            expert_parallel=cfg.num_experts % 16 == 0)
    if cfg.family == "hybrid":
        cfg = dataclasses.replace(cfg, mamba_seq_chunk=512)  # P7
    return cfg


def _train_hyper(cfg: ArchConfig, opt: bool,
                 agent_mode: str = "rows") -> InteractConfig:
    return InteractConfig(
        alpha=1e-2, beta=0.5,
        hyper=BilevelHyper(mu_g=0.1, neumann_k=2, lipschitz_g=2.0,
                           ce_chunk=512, remat=True,
                           attn_impl="blockwise" if opt else "reference",
                           seq_shard=opt and agent_mode == "rows"
                           and cfg.family in ("dense", "vlm", "audio"),
                           batch_shard=agent_mode == "pods",
                           microbatch=4 if opt else 1))


def lower_train(cfg: ArchConfig, mesh, opt: bool = False,
                agent_mode: str = "rows"):
    if opt:
        cfg = optimized_config(cfg)
    icfg = _train_hyper(cfg, opt, agent_mode)
    step = make_train_step(cfg, mesh, icfg, agent_mode=agent_mode)
    if agent_mode == "pods":
        from repro.train.step import init_train_state
        m_agents = mesh.shape.get("pod", 1)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        state_sh = jax.eval_shape(
            lambda k: init_train_state(cfg, k, m_agents), key)
    else:
        state_sh = specs_mod.state_shapes(cfg, mesh)
    st_specs = train_state_specs(state_sh, mesh, agent_mode=agent_mode)
    st_shardings = tree_shardings(mesh, st_specs)
    if agent_mode == "pods":
        sd = specs_mod.SHAPES["train_4k"]
        m_agents = mesh.shape.get("pod", 1)
        inputs = {"tokens": jax.ShapeDtypeStruct(
            (m_agents, sd.global_batch // m_agents, sd.seq_len), jnp.int32)}
        tok_shard = NamedSharding(mesh, P("pod", "data"))
        a_axes = ("pod",)
        aent = "pod"
    else:
        inputs = specs_mod.train_inputs(cfg, mesh)
        a_axes = agent_axes(mesh)
        aent = a_axes if len(a_axes) > 1 else a_axes[0]
        tok_shard = NamedSharding(mesh, P(aent))
    args = [state_sh, inputs["tokens"]]
    in_shardings = [st_shardings, tok_shard]
    if "prefix" in inputs:
        args.append(inputs["prefix"])
        in_shardings.append(NamedSharding(mesh, P(aent)))
    jitted = jax.jit(
        step,
        in_shardings=tuple(in_shardings),
        out_shardings=(st_shardings,
                       {"outer_ce": NamedSharding(mesh, P()),
                        "grad_norm": NamedSharding(mesh, P())}),
        donate_argnums=(0,),
    )
    with jax.set_mesh(mesh):
        return jitted.lower(*args)


def lower_prefill(cfg: ArchConfig, mesh, opt: bool = False):
    if opt:
        cfg = optimized_config(cfg)
    data_axes = agent_axes(mesh)  # batch over data (+pod)
    dent = data_axes if len(data_axes) > 1 else data_axes[0]
    params_sh = specs_mod.params_shapes(cfg, with_head=True)
    p_specs = tree_specs(params_sh, mesh.shape["model"])
    p_shardings = tree_shardings(mesh, p_specs)
    inputs = specs_mod.prefill_inputs(cfg)
    # P4 refuted for prefill (wire regression, EXPERIMENTS.md): never here.
    fn = make_prefill_step(cfg, attn_impl="blockwise" if opt else "reference",
                           seq_shard=False)
    args = [params_sh, inputs["tokens"]]
    in_sh = [p_shardings, NamedSharding(mesh, P(dent))]
    if "prefix" in inputs:
        args.append(inputs["prefix"])
        in_sh.append(NamedSharding(mesh, P(dent)))
    jitted = jax.jit(fn, in_shardings=tuple(in_sh),
                     out_shardings=NamedSharding(mesh, P(dent, "model")))
    with jax.set_mesh(mesh):
        return jitted.lower(*args)


def lower_decode(cfg: ArchConfig, mesh, shape: str, opt: bool = False):
    if opt:
        cfg = optimized_config(cfg)
    if shape == "long_500k":
        cfg = specs_mod.long_context_config(cfg)
    sd = specs_mod.SHAPES[shape]
    params_sh = specs_mod.params_shapes(cfg, with_head=True)
    p_shardings = tree_shardings(mesh, tree_specs(params_sh,
                                                  mesh.shape["model"]))
    inputs = specs_mod.decode_inputs(cfg, shape)
    c_specs = cache_specs(inputs["cache"], mesh, sd.global_batch)
    c_shardings = tree_shardings(mesh, c_specs)
    data_axes = agent_axes(mesh)
    dent = data_axes if len(data_axes) > 1 else data_axes[0]
    batch_shardable = sd.global_batch % int(
        np.prod([mesh.shape[a] for a in data_axes])) == 0
    tok_spec = P(dent) if batch_shardable else P()
    fn = make_serve_step(cfg)
    jitted = jax.jit(
        fn,
        in_shardings=(p_shardings, NamedSharding(mesh, tok_spec),
                      c_shardings, NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, tok_spec), c_shardings),
        donate_argnums=(2,),
    )
    with jax.set_mesh(mesh):
        return jitted.lower(params_sh, inputs["token"], inputs["cache"],
                            inputs["position"])


def run_one(arch: str, shape: str, multi_pod: bool,
            save: bool = True, opt: bool = False,
            agent_mode: str = "rows") -> dict[str, Any]:
    cfg = get_config(arch)
    ok, why = specs_mod.shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "skipped": True, "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if shape == "train_4k":
        lowered = lower_train(cfg, mesh, opt=opt, agent_mode=agent_mode)
    elif shape == "prefill_32k":
        lowered = lower_prefill(cfg, mesh, opt=opt)
    else:
        lowered = lower_decode(cfg, mesh, shape, opt=opt)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = normalize_cost_analysis(compiled.cost_analysis())
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    n_dev = int(np.prod(list(mesh.shape.values())))
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": dict(mesh.shape),
        "devices": n_dev,
        "skipped": False,
        "optimized": opt,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "cost": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
        "collectives": coll,
    }
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        tag = "multipod" if multi_pod else "pod"
        if opt:
            tag += "-opt"
        if agent_mode == "pods":
            tag += "-agentpods"
        out = RESULTS_DIR / f"{arch}__{shape}__{tag}.json"
        out.write_text(json.dumps(result, indent=2))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(specs_mod.SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="lower the beyond-paper optimized variant")
    ap.add_argument("--agents-per-pod", action="store_true",
                    help="P6 layout: agents = pods, FSDP inside the pod "
                         "(requires --multi-pod)")
    args = ap.parse_args()

    combos = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in specs_mod.SHAPES:
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        try:
            res = run_one(arch, shape, args.multi_pod, opt=args.opt,
                          agent_mode="pods" if args.agents_per_pod
                          else "rows")
        except Exception as e:  # keep sweeping; report at the end
            failures.append((arch, shape, repr(e)[:300]))
            print(f"[FAIL] {arch} x {shape}: {e!r}"[:400], flush=True)
            continue
        if res.get("skipped"):
            print(f"[SKIP] {arch} x {shape}: {res['reason']}")
            continue
        mem = res["memory"]
        arg_gb = (mem["argument_size_bytes"] or 0) / 2**30
        tmp_gb = (mem["temp_size_bytes"] or 0) / 2**30
        print(f"[OK] {arch} x {shape} ({'2x16x16' if args.multi_pod else '16x16'}): "
              f"compile {res['compile_s']}s, args {arg_gb:.2f} GiB/dev, "
              f"temps {tmp_gb:.2f} GiB/dev, flops {res['cost']['flops']:.3e}, "
              f"wire {res['collectives']['wire_bytes'] / 2**30:.3f} GiB",
              flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for arch, shape, err in failures:
            print(f"  {arch} x {shape}: {err}")
        raise SystemExit(1)
    print("\nall combinations lowered and compiled.")


if __name__ == "__main__":
    main()
