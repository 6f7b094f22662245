"""Model builder: ArchConfig -> init / features / logits / decode.

Layer stacks are executed as ``lax.scan`` over *periods* (see base.py) with
parameters stacked on a leading period axis — one period may contain
several heterogeneous layers (jamba: 1 attention + 7 mamba).  This keeps
HLO size O(period) instead of O(num_layers) and is what makes 72-layer
dry-runs compile in reasonable time.

Bilevel split: ``features()`` returns final hidden states produced by the
*backbone* (the outer variable x of the paper); the LM head is a separate
parameter (the inner variable y_i, per-agent).  ``init_head`` /
``head_logits`` implement that readout.  For non-bilevel use,
``init_params`` can include a head and ``forward`` goes end to end.

VLM / audio frontends are stubs per the assignment: ``prefix_embed``
(precomputed patch/frame embeddings) is projected and prepended to the
token embeddings.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.base import ArchConfig, LayerSpec
from repro.models import layers as L
from repro.models import mamba as Mb
from repro.models import moe as Moe
from repro.models import rwkv as Rk

__all__ = [
    "init_params", "init_head", "features", "head_logits", "forward",
    "lm_loss", "init_cache", "decode_step", "prefill", "param_count",
]


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ArchConfig, spec: LayerSpec, key) -> dict:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 4)
    p: dict[str, Any] = {"pre_norm": L.init_rms_norm(cfg.d_model, dt),
                         "post_norm": L.init_rms_norm(cfg.d_model, dt)}
    if spec.mixer == "attn":
        p["attn"] = L.init_attention(
            keys[0], cfg.d_model, cfg.held_heads, cfg.held_kv_heads,
            cfg.resolved_head_dim, cfg.qk_norm, dt)
    elif spec.mixer == "mamba":
        p["mamba"] = Mb.init_mamba(keys[0], cfg.d_model, cfg.mamba_d_state,
                                   cfg.mamba_d_conv, cfg.held_d_inner,
                                   cfg.resolved_dt_rank, dt)
    elif spec.mixer == "rwkv":
        p["rwkv"] = Rk.init_rwkv_block(keys[0], cfg.d_model,
                                       cfg.rwkv_head_size, dt, cfg.d_ff)
    if spec.ffn == "dense" and spec.mixer != "rwkv":
        p["mlp"] = L.init_mlp(keys[1], cfg.d_model, cfg.held_d_ff, dt)
    elif spec.ffn == "moe":
        p["moe"] = Moe.init_moe(keys[1], cfg.d_model, cfg.d_ff,
                                cfg.num_experts, dt)
    return p


def init_params(cfg: ArchConfig, key, with_head: bool = False) -> dict:
    """Backbone parameters: ``layers`` a list of the period's runs
    (``ArchConfig.layer_runs``), each leaf (periods, run length, ...)."""
    cfg.validate()
    dt = _dtype(cfg)
    n_periods = cfg.num_periods()
    k_embed, k_layers, k_head, k_front = jax.random.split(key, 4)

    params: dict[str, Any] = {
        "embed": (jax.random.normal(k_embed, (cfg.held_vocab, cfg.d_model))
                  * (1.0 / jnp.sqrt(cfg.d_model))).astype(dt),
        "final_norm": L.init_rms_norm(cfg.d_model, dt),
    }

    def init_period(k):
        runs = cfg.layer_runs()
        ks = jax.random.split(k, len(runs))
        return [jax.vmap(lambda kk, spec=spec: _init_layer(cfg, spec, kk))(
                    jax.random.split(ks[i], n))
                for i, (spec, n) in enumerate(runs)]

    period_keys = jax.random.split(k_layers, n_periods)
    stacked = jax.vmap(init_period)(period_keys)
    params["layers"] = stacked

    if cfg.frontend != "none" and cfg.num_prefix_tokens:
        fd = cfg.frontend_dim or cfg.d_model
        params["frontend_proj"] = (
            jax.random.normal(k_front, (fd, cfg.d_model))
            * (1.0 / jnp.sqrt(fd))).astype(dt)
    if with_head:
        params["head"] = init_head(cfg, k_head)
    return params


def init_head(cfg: ArchConfig, key) -> jax.Array:
    """The inner-variable readout head y (d_model, vocab held here)."""
    return (jax.random.normal(key, (cfg.d_model, cfg.held_vocab))
            * (1.0 / jnp.sqrt(cfg.d_model))).astype(_dtype(cfg))


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _apply_layer(cfg: ArchConfig, spec: LayerSpec, p: dict, x: jax.Array,
                 positions: jax.Array, impl: str,
                 cache: dict | None = None,
                 moe_impl: str = "capacity") -> tuple[jax.Array, dict | None, jax.Array]:
    """Pre-norm residual layer. Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), dtype=jnp.float32)
    h = L.rms_norm(p["pre_norm"], x, cfg.norm_eps)
    new_cache = cache
    if spec.mixer == "attn":
        window = spec.sliding_window
        if cfg.long_context_mode == "window" and window is None:
            window = cfg.local_window
        out, new_attn = L.attention(
            p["attn"], h, positions,
            num_heads=cfg.held_heads, num_kv_heads=cfg.held_kv_heads,
            head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta if cfg.use_rope else None,
            window=window, logit_softcap=cfg.attn_logit_softcap,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
            cache=None if cache is None else cache["attn"], impl=impl)
        if cache is not None:
            new_cache = {**cache, "attn": new_attn}
    elif spec.mixer == "mamba":
        if cache is None:
            out = Mb.mamba_block(p["mamba"], h,
                                 seq_chunk=cfg.mamba_seq_chunk or None,
                                 eps=cfg.norm_eps)
        elif h.shape[1] > 1:  # prefill into a fresh cache
            out, st = Mb.mamba_prefill(p["mamba"], h, eps=cfg.norm_eps)
            new_cache = {**cache, "mamba": st}
        else:
            out, st = Mb.mamba_decode_step(p["mamba"], h, cache["mamba"],
                                           eps=cfg.norm_eps)
            new_cache = {**cache, "mamba": st}
    elif spec.mixer == "rwkv":
        if cache is None:
            out, _, _ = Rk.rwkv_time_mix(p["rwkv"], h, cfg.rwkv_head_size,
                                         impl=impl)
        else:
            out, st = Rk.rwkv_time_mix_decode(p["rwkv"], h,
                                              cfg.rwkv_head_size,
                                              cache["rwkv"])
            new_cache = {**cache, "rwkv": st}
    else:
        raise ValueError(spec.mixer)
    x = x + out

    h = L.rms_norm(p["post_norm"], x, cfg.norm_eps)
    if spec.mixer == "rwkv" and spec.ffn == "dense":
        # RWKV uses its own token-shifted channel mix as the FFN.
        if cache is None:
            out, _ = Rk.rwkv_channel_mix(p["rwkv"], h)
        else:
            out, cm_last = Rk.rwkv_channel_mix(
                p["rwkv"], h, x_last=new_cache["rwkv"]["cm_last"].astype(h.dtype))
            new_cache = {**new_cache,
                         "rwkv": {**new_cache["rwkv"],
                                  "cm_last": cm_last.astype(jnp.float32)}}
        return x + out, new_cache, aux
    if spec.ffn == "dense":
        out = L.gated_mlp(p["mlp"], h)
    elif spec.ffn == "moe":
        if moe_impl == "exact":
            out, aux = Moe.moe_ffn_exact(p["moe"], h,
                                         num_experts=cfg.num_experts,
                                         top_k=cfg.experts_per_token)
        else:
            out, aux = Moe.moe_ffn(p["moe"], h, num_experts=cfg.num_experts,
                                   top_k=cfg.experts_per_token,
                                   capacity_factor=cfg.capacity_factor,
                                   token_chunk=cfg.moe_token_chunk or None,
                                   expert_parallel=cfg.expert_parallel)
    else:
        out = jnp.zeros_like(h)
    return x + out, new_cache, aux


def _embed(cfg: ArchConfig, params: dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(float(cfg.d_model)).astype(_dtype(cfg))
    return x


def _embed_inputs(cfg: ArchConfig, params: dict, tokens: jax.Array,
                  prefix_embed: jax.Array | None) -> jax.Array:
    x = _embed(cfg, params, tokens)
    if prefix_embed is not None:
        proj = params.get("frontend_proj")
        pre = prefix_embed.astype(x.dtype)
        if proj is not None:
            pre = pre @ proj
        x = jnp.concatenate([pre, x], axis=1)
    return x


def features(cfg: ArchConfig, params: dict, tokens: jax.Array,
             prefix_embed: jax.Array | None = None,
             impl: str = "reference", remat: bool = True,
             moe_impl: str = "capacity",
             act_spec=None) -> tuple[jax.Array, jax.Array]:
    """Backbone features: (batch, seq[, +prefix], d_model), plus MoE aux loss.

    ``act_spec``: optional PartitionSpec applied to the residual stream at
    every period boundary (sequence parallelism — perf iteration P4): the
    tensors *saved for backward* live sequence-sharded over the model
    axis; XLA gathers heads/kv only where attention needs them.
    """
    x = _embed_inputs(cfg, params, tokens, prefix_embed)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def constrain(h):
        if act_spec is None:
            return h
        return jax.lax.with_sharding_constraint(h, act_spec)

    def layer_fn(spec):
        def apply(p, h):
            h, _, a = _apply_layer(cfg, spec, p, h, positions, impl,
                                   moe_impl=moe_impl)
            return h, a
        # remat per layer: a backward holds one layer's activations
        return jax.checkpoint(apply) if remat else apply

    runs = cfg.layer_runs()
    layer_fns = [layer_fn(spec) for spec, _ in runs]

    def run_body(fn):
        def body(carry, p):
            h, a = fn(p, carry[0])
            return (h, carry[1] + a), None
        return body

    def period_body(carry, period_params):
        for fn, run_params in zip(layer_fns, period_params):
            carry, _ = jax.lax.scan(run_body(fn), carry, run_params)
        h, aux = carry
        return (constrain(h), aux), None

    carry0 = (constrain(x), jnp.zeros((), jnp.float32))
    (x, aux), _ = jax.lax.scan(period_body, carry0, params["layers"])
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def head_logits(cfg: ArchConfig, head: jax.Array, feats: jax.Array) -> jax.Array:
    logits = L.matmul(feats, head)
    return L.softcap(logits, cfg.final_logit_softcap)


def forward(cfg: ArchConfig, params: dict, tokens: jax.Array,
            prefix_embed: jax.Array | None = None,
            impl: str = "reference", remat: bool = True,
            moe_impl: str = "capacity") -> tuple[jax.Array, jax.Array]:
    feats, aux = features(cfg, params, tokens, prefix_embed, impl, remat,
                          moe_impl)
    head = params["head"] if "head" in params else params["embed"].T
    return head_logits(cfg, head, feats), aux


def lm_loss(cfg: ArchConfig, logits: jax.Array, labels: jax.Array,
            aux: jax.Array | None = None) -> jax.Array:
    """Next-token CE; labels aligned with the *token* part of the sequence."""
    n_pre = logits.shape[1] - labels.shape[1]
    logits = logits[:, n_pre:, :]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp[:, :-1], labels[:, 1:, None], axis=-1)
    loss = jnp.mean(nll)
    if aux is not None:
        loss = loss + cfg.router_aux_weight * aux
    return loss


def param_count(params) -> int:
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg: ArchConfig, spec: LayerSpec, batch: int,
                      max_len: int) -> dict:
    dt = _dtype(cfg)
    cache: dict[str, Any] = {}
    if spec.mixer == "attn":
        window = spec.sliding_window
        if cfg.long_context_mode == "window" and window is None:
            window = cfg.local_window
        # SWA layers only ever need `window` cache slots; full layers need
        # the whole sequence.  Bounded caches are what keep mixtral/gemma2
        # long_500k sub-quadratic in memory.
        size = max_len if window is None else min(max_len, window)
        cache["attn"] = {
            "k": jnp.zeros((batch, size, cfg.held_kv_heads,
                            cfg.resolved_head_dim), dtype=dt),
            "v": jnp.zeros((batch, size, cfg.held_kv_heads,
                            cfg.resolved_head_dim), dtype=dt),
            "len": jnp.zeros((), jnp.int32),
        }
    elif spec.mixer == "mamba":
        cache["mamba"] = Mb.init_mamba_state(
            batch, cfg.held_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, dt)
    elif spec.mixer == "rwkv":
        cache["rwkv"] = Rk.init_rwkv_state(batch, cfg.d_model,
                                           cfg.rwkv_head_size)
    return cache


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> list:
    """Per-period-position caches stacked over periods (scan-compatible)."""
    pattern = cfg.layer_pattern()
    n = cfg.num_periods()

    def one_period(_):
        return [_init_layer_cache(cfg, spec, batch, max_len)
                for spec in pattern]

    return jax.vmap(one_period)(jnp.arange(n))


def prefill(cfg: ArchConfig, params: dict, head: jax.Array | None,
            tokens: jax.Array, cache,
            impl: str = "reference") -> tuple[jax.Array, Any]:
    """Fused prefill: full-sequence forward that POPULATES a fresh decode
    cache (KV ring buffers laid out for continuation, SSM states at the
    last token).  Returns (last-token logits (batch, vocab), cache)."""
    s = tokens.shape[1]
    logits, new_cache = decode_step(
        cfg, params, head, tokens, cache,
        jnp.arange(s, dtype=jnp.int32), impl=impl)
    return logits[:, -1, :], new_cache


def decode_step(cfg: ArchConfig, params: dict, head: jax.Array | None,
                token: jax.Array, cache, position: jax.Array,
                impl: str = "reference") -> tuple[jax.Array, Any]:
    """One-token decode.  token: (batch, 1) int32; position: scalar int32
    (or an (s,) position vector for the fused-prefill path).

    Returns (logits (batch, s, vocab), new_cache).
    """
    x = _embed(cfg, params, token)
    positions = position[None] if position.ndim == 0 else position
    # (run, index in the run) of each layer of the period, in order
    layers = [(r, j) for r, (_, n) in enumerate(cfg.layer_runs())
              for j in range(n)]
    pattern = cfg.layer_pattern()

    def period_body(h, scanned):
        period_params, period_cache = scanned
        new_caches = []
        for i, (spec, (r, j)) in enumerate(zip(pattern, layers)):
            p = jax.tree_util.tree_map(lambda l: l[j], period_params[r])
            h, nc, _ = _apply_layer(cfg, spec, p, h, positions, impl,
                                    cache=period_cache[i], moe_impl="exact")
            new_caches.append(nc)
        return h, new_caches

    x, new_cache = jax.lax.scan(period_body, x, (params["layers"], cache))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    if head is None:
        head = params["head"] if "head" in params else params["embed"].T
    return head_logits(cfg, head, x), new_cache
