"""Three-term roofline analysis from the dry-run artifacts.

    compute term    = HLO_FLOPs    / (peak_FLOP/s)          [per chip]
    memory term     = HLO_bytes    / (HBM_bw)               [per chip]
    collective term = wire_bytes   / (link_bw)              [per chip]

Sources: ``compiled.cost_analysis()`` (per-device SPMD module) for FLOPs
and bytes; collective wire bytes parsed from the optimized HLO
(repro.launch.dryrun.parse_collectives) with ring weights (all-reduce 2x).

Hardware model (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI.

Caveat measured in-tree (tests/test_roofline.py): XLA's cost analysis
counts a while-loop body ONCE, not times its trip count.  Our models run
layers as a scan over periods, so raw FLOPs/bytes would undercount by
~num_periods.  ``scan_corrected_*`` multiplies the dominant loop's share
back in using the known period count; both raw and corrected numbers are
reported.

MODEL_FLOPS uses the standard 6*N*D (dense train), 2*N*D (inference
forward), with N_active for MoE — the "useful FLOPs" yardstick; the ratio
MODEL_FLOPS / HLO_FLOPs exposes remat/redundancy overhead.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any

import numpy as np

from repro.models.base import ArchConfig

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
LINK_BW = 50e9             # bytes/s / ICI link

__all__ = [
    "analytic_param_count", "active_param_count", "model_flops",
    "normalize_cost_analysis", "roofline_terms", "RooflineReport",
    "load_dryrun", "report_table",
]


def normalize_cost_analysis(cost) -> dict:
    """Normalize ``compiled.cost_analysis()`` across JAX versions.

    Newer JAX returns a flat dict; older versions return a one-element
    list of dicts (one per executable program).  Always returns a dict so
    callers can index properties (``"flops"``, ``"bytes accessed"``, ...)
    without version checks.
    """
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost)


def _attn_params(cfg: ArchConfig) -> int:
    hd = cfg.resolved_head_dim
    return cfg.d_model * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)


def _mlp_params(cfg: ArchConfig) -> int:
    return 3 * cfg.d_model * cfg.d_ff


def _moe_params(cfg: ArchConfig) -> int:
    return (cfg.num_experts * 3 * cfg.d_model * cfg.d_ff
            + cfg.d_model * cfg.num_experts)


def _mamba_params(cfg: ArchConfig) -> int:
    d_in = cfg.mamba_expand * cfg.d_model
    r, n = cfg.resolved_dt_rank, cfg.mamba_d_state
    return (cfg.d_model * 2 * d_in                 # in_proj
            + (cfg.mamba_d_conv + 1) * d_in        # conv + bias
            + d_in * (r + 2 * n) + r + 2 * n       # x_proj, dt/B/C norms
            + r * d_in + d_in                      # dt_proj + bias
            + d_in * (n + 1)                       # A, D
            + d_in * cfg.d_model)                  # out_proj


def _rwkv_params(cfg: ArchConfig) -> int:
    d = cfg.d_model
    return 6 * d * d + 2 * d * cfg.d_ff + d * d + 10 * d


def analytic_param_count(cfg: ArchConfig, active: bool = False) -> int:
    """Backbone + head parameter count from the config alone."""
    total = cfg.vocab_size * cfg.d_model * 2  # embed + (untied) head
    if cfg.frontend != "none" and cfg.num_prefix_tokens:
        total += (cfg.frontend_dim or cfg.d_model) * cfg.d_model
    for spec in cfg.layer_pattern():
        n_of_this = cfg.num_layers // len(cfg.layer_pattern())
        layer = 0
        if spec.mixer == "attn":
            layer += _attn_params(cfg)
        elif spec.mixer == "mamba":
            layer += _mamba_params(cfg)
        elif spec.mixer == "rwkv":
            layer += _rwkv_params(cfg)
        if spec.ffn == "dense" and spec.mixer != "rwkv":
            layer += _mlp_params(cfg)
        elif spec.ffn == "moe":
            if active:
                frac = cfg.experts_per_token / cfg.num_experts
                layer += int(_moe_params(cfg) * frac)
            else:
                layer += _moe_params(cfg)
        total += layer * n_of_this
    return total


def active_param_count(cfg: ArchConfig) -> int:
    return analytic_param_count(cfg, active=True)


def model_flops(cfg: ArchConfig, shape_kind: str, seq_len: int,
                global_batch: int) -> float:
    """Useful model FLOPs for the whole step, all chips.

    train:    6 * N_active * D  (fwd 2ND + bwd 4ND), D = global tokens.
              The INTERACT step runs ~2 fwd+bwd passes (outer + cross) on
              half the batch each + 1 forward => ~1.25x of a plain step;
              we report plain 6ND as the conventional yardstick.
    prefill:  2 * N_active * D
    decode:   2 * N_active * B  (one token per request)
    """
    n = active_param_count(cfg)
    if shape_kind == "train":
        d = seq_len * global_batch
        return 6.0 * n * d
    if shape_kind == "prefill":
        return 2.0 * n * seq_len * global_batch
    return 2.0 * n * global_batch


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    devices: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_total: float
    useful_ratio: float
    scan_corrected: bool
    raw: dict

    def row(self) -> str:
        return (f"{self.arch:22s} {self.shape:12s} "
                f"{self.compute_s:10.3e} {self.memory_s:10.3e} "
                f"{self.collective_s:10.3e}  {self.dominant:10s} "
                f"{self.useful_ratio:6.2f}")


def roofline_terms(result: dict, cfg: ArchConfig,
                   scan_trip_correction: float | None = None
                   ) -> RooflineReport:
    """Build the three terms from one dry-run JSON record."""
    from repro.launch.input_specs import SHAPES
    sd = SHAPES[result["shape"]]
    devices = result["devices"]
    flops_dev = float(result["cost"]["flops"] or 0.0)
    bytes_dev = float(result["cost"]["bytes_accessed"] or 0.0)
    wire_dev = float(result["collectives"]["wire_bytes"] or 0.0)

    corr = 1.0
    corrected = False
    if scan_trip_correction and scan_trip_correction > 1.0:
        corr = scan_trip_correction
        corrected = True

    compute_s = flops_dev * corr / PEAK_FLOPS
    memory_s = bytes_dev * corr / HBM_BW
    collective_s = wire_dev / LINK_BW

    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, sd.kind, sd.seq_len, sd.global_batch)
    hlo_total = flops_dev * corr * devices
    ratio = mf / hlo_total if hlo_total else float("nan")

    return RooflineReport(
        arch=result["arch"], shape=result["shape"], devices=devices,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=mf, hlo_flops_total=hlo_total,
        useful_ratio=ratio, scan_corrected=corrected, raw=result)


def load_dryrun(results_dir: str | pathlib.Path, tag: str = "pod"
                ) -> list[dict]:
    out = []
    for p in sorted(pathlib.Path(results_dir).glob(f"*__{tag}.json")):
        out.append(json.loads(p.read_text()))
    return out


def report_table(reports: list[RooflineReport]) -> str:
    header = (f"{'arch':22s} {'shape':12s} {'compute_s':>10s} "
              f"{'memory_s':>10s} {'collect_s':>10s}  {'dominant':10s} "
              f"{'useful':>6s}")
    lines = [header, "-" * len(header)]
    lines += [r.row() for r in reports]
    return "\n".join(lines)
