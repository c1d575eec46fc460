"""Unified decoder LM covering the dense / MoE / MLA / VLM / SSM / hybrid
families, with scan-over-layers, KV/SSM caches, prefill and one-token decode.

API (pure functions over nested-dict params):
    init_params(cfg, key, abstract=False)           -> params
    forward(params, cfg, tokens, ctx, extra_embeds) -> logits [B, T, V]
    init_cache(cfg, batch, max_len, abstract=False) -> cache
    prefill(params, cfg, tokens, cache, ctx, ...)   -> (logits, cache)
    decode_step(params, cfg, token, position, cache, ctx) -> (logits, cache)

Layer stacking: homogeneous groups are stacked on a leading `layers` dim and
folded with `lax.scan` (compile time independent of depth — essential for
lowering llama3-405B's 126 layers 80x in the dry-run). The zamba2 hybrid
runs a Python loop of [6-mamba-scan + shared-attn] super-blocks because its
attention block re-uses ONE weight set (scan xs can't express weight tying).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import ssm as ssm_lib
from repro.models.common import (ParamFactory, embed_tokens, init_embedding,
                                 init_rms_norm, rms_norm, rope_inv_freq,
                                 unembed)
from repro.sharding import ParallelContext


# ---------------------------------------------------------------------------
# Config adapters
# ---------------------------------------------------------------------------

def attn_config(cfg: ModelConfig, cross: bool = False) -> attn_lib.AttnConfig:
    mla = cfg.mla
    return attn_lib.AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        attn_chunk=cfg.attn_chunk or None,
        sliding_window=cfg.sliding_window,
        q_lora=mla.q_lora if mla else 0,
        kv_lora=mla.kv_lora if mla else 0,
        rope_dim=mla.rope_dim if mla else 64,
        v_head_dim=mla.v_head_dim if mla else 0,
    )


def mlp_config(cfg: ModelConfig, activation: str = "swiglu") -> ffn_lib.MLPConfig:
    return ffn_lib.MLPConfig(cfg.d_model, cfg.d_ff, activation)


def moe_config(cfg: ModelConfig) -> ffn_lib.MoEConfig:
    m = cfg.moe
    return ffn_lib.MoEConfig(
        d_model=cfg.d_model, d_ff=m.d_ff_expert, n_experts=m.n_experts,
        top_k=m.top_k, n_shared_experts=m.n_shared_experts,
        shared_d_ff=m.shared_d_ff, capacity_factor=m.capacity_factor,
        router_aux_weight=m.router_aux_weight, n_held=m.n_held,
        first_held=m.first_held)


def held_experts(cfg: ModelConfig) -> bool:
    """Does every MoE layer hold only a share of the experts (and report
    per-layer expert counters from its decode step)?"""
    return cfg.moe is not None and cfg.moe.n_held is not None


FULL_WINDOW = 1 << 30       # the traced window of a layer with none


def layer_kind_arrays(cfg: ModelConfig, lo: int, hi: int) -> dict:
    """Layers [lo, hi)'s attention kinds (`cfg.layer_kinds`, repeated) as
    stacked arrays, scanned beside the layers' weights: "window" [n]
    int32 (FULL_WINDOW for a full layer), "inv_freq" [n, head_dim/2] and
    "rope_scale" [n] float32."""
    kinds = [cfg.layer_kinds[i % len(cfg.layer_kinds)] for i in range(lo, hi)]
    ropes = [rope_inv_freq(cfg.head_dim, cfg.rope_theta, k.rope)
             for k in kinds]
    return {"window": jnp.asarray([k.window or FULL_WINDOW for k in kinds],
                                  jnp.int32),
            "inv_freq": jnp.asarray(np.stack([f for f, _ in ropes])),
            "rope_scale": jnp.asarray([c for _, c in ropes], jnp.float32)}


def rwkv_config(cfg: ModelConfig) -> ssm_lib.RWKVConfig:
    return ssm_lib.RWKVConfig(cfg.d_model, cfg.d_ff, head_dim=cfg.head_dim
                              if cfg.head_dim <= cfg.d_model else 64)


def mamba_config(cfg: ModelConfig) -> ssm_lib.Mamba2Config:
    return ssm_lib.Mamba2Config(cfg.d_model, d_state=cfg.ssm_state,
                                head_dim=cfg.ssm_head_dim)


def _group_sizes(cfg: ModelConfig) -> Dict[str, int]:
    """Stacked layer-group sizes per family."""
    if cfg.family == "moe":
        nd = cfg.moe.n_dense_layers
        return {"dense": nd, "moe": cfg.n_layers - nd}
    if cfg.family in ("dense", "vlm", "encdec"):
        return {"dense": cfg.n_layers}
    if cfg.family == "ssm_rwkv":
        return {"rwkv": cfg.n_layers}
    if cfg.family == "hybrid":
        return {"mamba": cfg.n_layers}
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_attn_block(pf: ParamFactory, cfg: ModelConfig, n: int) -> dict:
    ac = attn_config(cfg)
    init_attn = attn_lib.init_mla if cfg.mla else attn_lib.init_gqa
    return {
        "norm1": init_rms_norm(pf, "norm1", cfg.d_model, stacked=n),
        "attn": init_attn(pf.scope("attn"), ac, stacked=n),
        "norm2": init_rms_norm(pf, "norm2", cfg.d_model, stacked=n),
    }


def init_params(cfg: ModelConfig, key, abstract: bool = False) -> dict:
    pf = ParamFactory(None if abstract else key, cfg.pdtype(), abstract)
    params: Dict[str, Any] = {
        "embedding": init_embedding(pf, cfg.vocab_size, cfg.d_model),
        "final_norm": init_rms_norm(pf, "final_norm", cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = pf.param(
            "unembed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
            fan_in=cfg.d_model)
    groups = _group_sizes(cfg)
    blocks: Dict[str, Any] = {}
    if "dense" in groups and groups["dense"]:
        n = groups["dense"]
        act = "gelu" if cfg.family == "encdec" else "swiglu"
        b = _init_attn_block(pf.scope("dense"), cfg, n)
        b["mlp"] = ffn_lib.init_mlp(pf.scope("dense_mlp"),
                                    mlp_config(cfg, act), n)
        blocks["dense"] = b
    if "moe" in groups and groups["moe"]:
        n = groups["moe"]
        b = _init_attn_block(pf.scope("moe"), cfg, n)
        b["moe"] = ffn_lib.init_moe(pf.scope("moe_ffn"), moe_config(cfg), n)
        blocks["moe"] = b
    if "rwkv" in groups:
        blocks["rwkv"] = ssm_lib.init_rwkv_block(
            pf.scope("rwkv"), rwkv_config(cfg), stacked=groups["rwkv"])
    if "mamba" in groups:
        blocks["mamba"] = ssm_lib.init_mamba2_block(
            pf.scope("mamba"), mamba_config(cfg), stacked=groups["mamba"])
        if cfg.shared_attn_every:
            sb = _init_attn_block(pf.scope("shared"), cfg, 0)
            sb["mlp"] = ffn_lib.init_mlp(pf.scope("shared_mlp"),
                                         mlp_config(cfg), 0)
            blocks["shared_attn"] = sb
    params["blocks"] = blocks
    if cfg.encoder is not None:
        params["encoder"] = _init_encoder(pf.scope("encoder"), cfg)
    return params


def _init_encoder(pf: ParamFactory, cfg: ModelConfig) -> dict:
    """Bidirectional encoder stack (whisper-style, GELU MLP, no rope —
    sinusoidal positions added to the stub frame embeddings)."""
    n = cfg.encoder.n_layers
    b = _init_attn_block(pf, cfg, n)
    b["mlp"] = ffn_lib.init_mlp(pf.scope("enc_mlp"),
                                mlp_config(cfg, "gelu"), n)
    cross = attn_lib.init_cross_attn(pf.scope("cross"), attn_config(cfg),
                                     stacked=cfg.n_layers)
    return {"stack": b, "final_norm": init_rms_norm(pf, "enc_norm", cfg.d_model),
            "cross": cross,
            "cross_norm": init_rms_norm(pf, "cross_norm", cfg.d_model,
                                        stacked=cfg.n_layers)}


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False, dtype=None) -> dict:
    dtype = dtype or cfg.cdtype()
    groups = _group_sizes(cfg)
    cache: Dict[str, Any] = {}
    ac = attn_config(cfg)
    kv_len = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    if "dense" in groups and groups["dense"]:
        cache["dense"] = (attn_lib.init_mla_cache if cfg.mla else
                          attn_lib.init_gqa_cache)(
            ac, batch, kv_len, dtype, stacked=groups["dense"], abstract=abstract)
    if "moe" in groups and groups["moe"]:
        cache["moe"] = (attn_lib.init_mla_cache if cfg.mla else
                        attn_lib.init_gqa_cache)(
            ac, batch, kv_len, dtype, stacked=groups["moe"], abstract=abstract)
    if "rwkv" in groups:
        cache["rwkv"] = ssm_lib.init_rwkv_state(
            rwkv_config(cfg), batch, dtype, stacked=groups["rwkv"],
            abstract=abstract)
    if "mamba" in groups:
        cache["mamba"] = ssm_lib.init_mamba2_state(
            mamba_config(cfg), batch, dtype, stacked=groups["mamba"],
            abstract=abstract)
        if cfg.shared_attn_every:
            n_inv = cfg.n_layers // cfg.shared_attn_every
            sa_len = min(kv_len, 4096)   # shared attn uses windowed cache
            cache["shared_attn"] = attn_lib.init_gqa_cache(
                dataclasses.replace(ac, sliding_window=sa_len), batch, sa_len,
                dtype, stacked=n_inv, abstract=abstract)
    return cache


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(cfg.remat)


def _attn_ffn_block(p, cfg: ModelConfig, x, positions, ctx,
                    cache=None, cache_offset=0, decode=False, position=None,
                    ffn_kind="mlp", pages=None, paged_kernel=None,
                    layer=None, row_mask=None, experts=None):
    """One pre-norm transformer block (attention or MLA + dense/MoE FFN).
    Returns (x, new_cache, aux, stats). `pages` selects the block-paged
    cache layout, whose `cache` is then the group's whole stacked pool,
    read and written at this layer's index `p["layer"]` and returned
    whole; `paged_kernel` the Pallas-vs-XLA paged decode
    implementation (see models.attention) and, with held experts, the
    decode step's grouped-matmul kernel. `layer` holds the layer's
    attention kind as traced values (`layer_kind_arrays`); `row_mask` [B]
    marks live decode rows. A held-expert layer takes every layer's
    expert weights stacked (`experts`) and its index (`p["layer"]`).
    `stats` is int32 [hit, assign] of a held-expert layer
    (`ffn.moe_held_forward`), else zeros."""
    ac = attn_config(cfg)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    kw = {} if layer is None else {"layer": layer}
    if pages is not None:
        kw["layer_index"] = p["layer"]
    if decode:
        fwd = attn_lib.mla_decode if cfg.mla else attn_lib.gqa_decode
        y, new_cache = fwd(p["attn"], ac, h, position, cache, ctx,
                           pages=pages, paged_kernel=paged_kernel, **kw)
    else:
        fwd = attn_lib.mla_forward if cfg.mla else attn_lib.gqa_forward
        y, new_cache = fwd(p["attn"], ac, h, positions, ctx, cache,
                           cache_offset, pages=pages, **kw)
    x = x + y
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    stats = jnp.zeros((2,), jnp.int32)
    if ffn_kind == "moe" and held_experts(cfg):
        kernel = decode and kernel_ops.paged_kernel_enabled(paged_kernel)
        y, aux, stats = ffn_lib.moe_held_forward(
            {**p["moe"], **experts}, moe_config(cfg), h, row_mask,
            kernel=kernel, layer=p["layer"])
    elif ffn_kind == "moe":
        y, aux = ffn_lib.moe_forward(p["moe"], moe_config(cfg), h, ctx,
                                     decode=decode)
    else:
        y = ffn_lib.mlp_forward(p["mlp"], mlp_config(cfg), h, ctx)
    return x + y, new_cache, aux, stats


def _scan_group(block_fn, stacked_params, x, stacked_cache, cfg: ModelConfig,
                carry_cache: bool = False):
    """Fold a homogeneous stacked group. block_fn(p_layer, x, cache_layer) ->
    (x, new_cache_layer, aux, stats). Returns (x, new_stacked_cache,
    aux_sum, stats stacked per layer).

    With `carry_cache` (the paged pool) the stacked cache rides the scan's
    carry whole instead of being sliced per layer as `xs` and stacked back
    as `ys`: block_fn(p_layer, x, stacked_cache) updates its own layer in
    place (the layer's index rides in `p_layer`) and returns the stack."""
    if not cfg.scan_layers:
        n = jax.tree.leaves(stacked_params)[0].shape[0]
        blk = _maybe_remat(block_fn, cfg)
        caches, stats, aux_sum = [], [], jnp.zeros((), jnp.float32)
        for i in range(n):
            p_i = jax.tree.map(lambda a, i=i: a[i], stacked_params)
            if carry_cache:
                x, stacked_cache, aux, st = blk(p_i, x, stacked_cache)
            else:
                c_i = (None if stacked_cache is None
                       else jax.tree.map(lambda a, i=i: a[i], stacked_cache))
                x, nc, aux, st = blk(p_i, x, c_i)
                caches.append(nc)
            stats.append(st)
            aux_sum = aux_sum + aux
        new_cache = (stacked_cache if carry_cache or stacked_cache is None
                     else jax.tree.map(lambda *ls: jnp.stack(ls), *caches))
        return x, new_cache, aux_sum, jnp.stack(stats)

    if carry_cache:
        def body(carry, p_layer):
            x, aux_sum, cache = carry
            x, cache, aux, st = block_fn(p_layer, x, cache)
            return (x, aux_sum + aux, cache), st

        (x, aux_sum, new_cache), stats = jax.lax.scan(
            _maybe_remat(body, cfg),
            (x, jnp.zeros((), jnp.float32), stacked_cache), stacked_params)
        return x, new_cache, aux_sum, stats

    def body(carry, xs):
        x, aux_sum = carry
        if stacked_cache is None:
            p_layer, c_layer = xs, None
        else:
            p_layer, c_layer = xs
        x, new_c, aux, st = block_fn(p_layer, x, c_layer)
        return (x, aux_sum + aux), (new_c, st)

    wrapped = _maybe_remat(body, cfg)
    xs = stacked_params if stacked_cache is None else (stacked_params,
                                                       stacked_cache)
    (x, aux_sum), (new_cache, stats) = jax.lax.scan(
        wrapped, (x, jnp.zeros((), jnp.float32)), xs)
    return x, new_cache, aux_sum, stats


# ---------------------------------------------------------------------------
# Trunk
# ---------------------------------------------------------------------------

def _trunk(params, cfg: ModelConfig, x, positions, ctx,
           cache=None, cache_offset=0, decode=False, position=None,
           pages=None, paged_kernel=None, row_mask=None):
    """Runs all layer groups. x [B,T,d] embeddings. Returns (x, cache, aux,
    stats). `pages` [B, M] routes attention caches through a page table
    (every layer's blocks are indexed by the same table); the group's
    stacked pool then rides the layer scan's carry and each layer writes
    and reads its own blocks in place by index, so no layer's slice of
    the pool is copied out and stacked back;
    `paged_kernel` selects the Pallas paged-decode kernels per layer.
    With `cfg.layer_kinds` each layer's kind rides the scan beside its
    weights (`layer_kind_arrays`). `stats` is {} or, with held experts,
    {"moe": int32 [n_moe_layers, 2]} (hit, assign per layer)."""
    blocks = params["blocks"]
    new_cache: Dict[str, Any] = {}
    aux_total = jnp.zeros((), jnp.float32)
    groups = _group_sizes(cfg)
    stats: Dict[str, Any] = {}

    lo = 0
    for kind in ("dense", "moe"):
        if kind not in blocks or not groups.get(kind):
            continue
        n = groups[kind]
        kinds = (layer_kind_arrays(cfg, lo, lo + n) if cfg.layer_kinds
                 else None)
        lo += n
        xs, experts = blocks[kind], None
        c = cache.get(kind) if cache is not None else None
        carry = pages is not None and c is not None
        if kind == "moe" and held_experts(cfg):
            # held experts stay stacked outside the scan (a slice per
            # layer would be copied before the kernel reads it); the scan
            # carries each layer's index instead
            m = dict(xs["moe"])
            experts = {k: m.pop(k) for k in ("w_gate", "w_up", "w_down")}
            xs = {**xs, "moe": m}
        if carry or experts is not None:
            # the layer's index in its group: the held experts' and the
            # paged pool's
            xs = {**xs, "layer": jnp.arange(n)}

        def block_fn(p, x_, c_, _kind=kind, _experts=experts):
            p, layer = p if cfg.layer_kinds else (p, None)
            return _attn_ffn_block(p, cfg, x_, positions, ctx, c_,
                                   cache_offset, decode, position,
                                   ffn_kind=("moe" if _kind == "moe" else "mlp"),
                                   pages=pages, paged_kernel=paged_kernel,
                                   layer=layer, row_mask=row_mask,
                                   experts=_experts)
        xs = (xs, kinds) if cfg.layer_kinds else xs
        x, nc, aux, st = _scan_group(block_fn, xs, x, c, cfg,
                                     carry_cache=carry)
        if nc is not None:
            new_cache[kind] = nc
        if kind == "moe" and held_experts(cfg):
            stats["moe"] = st
        aux_total = aux_total + aux

    if "rwkv" in blocks:
        rc = rwkv_config(cfg)
        def rwkv_fn(p, x_, c_):
            x_, st = ssm_lib.rwkv_block_forward(p, rc, x_, ctx, c_)
            return x_, st, jnp.zeros((), jnp.float32), jnp.zeros((2,),
                                                                 jnp.int32)
        c = cache.get("rwkv") if cache is not None else None
        if c is None:   # states are mandatory carries; make fresh ones
            c = ssm_lib.init_rwkv_state(rc, x.shape[0], x.dtype,
                                        stacked=groups["rwkv"])
        x, nc, _, _ = _scan_group(rwkv_fn, blocks["rwkv"], x, c, cfg)
        new_cache["rwkv"] = nc

    if "mamba" in blocks:
        mc = mamba_config(cfg)
        n = groups["mamba"]
        every = cfg.shared_attn_every
        def mamba_fn(p, x_, c_):
            x_, st = ssm_lib.mamba2_block_forward(p, mc, x_, ctx, c_)
            return x_, st, jnp.zeros((), jnp.float32), jnp.zeros((2,),
                                                                 jnp.int32)
        c = cache.get("mamba") if cache is not None else None
        if c is None:
            c = ssm_lib.init_mamba2_state(mc, x.shape[0], x.dtype, stacked=n)
        if not every:
            x, nc, _, _ = _scan_group(mamba_fn, blocks["mamba"], x, c, cfg)
            new_cache["mamba"] = nc
        else:
            # zamba2: super-blocks of `every` mamba layers + SHARED attn block
            n_inv = n // every
            sa_cache = cache.get("shared_attn") if cache is not None else None
            sa_new, mamba_new = [], []
            sa_cfg = cfg.replace(sliding_window=(
                sa_cache["k"].shape[2] if sa_cache is not None else 4096))
            for g in range(n_inv + (1 if n % every else 0)):
                lo, hi = g * every, min((g + 1) * every, n)
                p_g = jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi],
                                   blocks["mamba"])
                c_g = jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi], c)
                x, nc_g, _, _ = _scan_group(mamba_fn, p_g, x, c_g, cfg)
                mamba_new.append(nc_g)
                if g < n_inv:
                    c_sa = (None if sa_cache is None else
                            jax.tree.map(lambda a, g=g: a[g], sa_cache))
                    x, nc_sa, _, _ = _attn_ffn_block(
                        blocks["shared_attn"], sa_cfg, x, positions, ctx,
                        c_sa, cache_offset, decode, position, ffn_kind="mlp")
                    if nc_sa is not None:
                        sa_new.append(nc_sa)
            new_cache["mamba"] = jax.tree.map(
                lambda *ls: jnp.concatenate(ls), *mamba_new)
            if sa_new:
                new_cache["shared_attn"] = jax.tree.map(
                    lambda *ls: jnp.stack(ls), *sa_new)
    return x, (new_cache if new_cache else None), aux_total, stats


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, tokens, extra_embeds, ctx):
    x = embed_tokens(params["embedding"], tokens).astype(cfg.cdtype())
    if extra_embeds is not None:
        # VLM: patch embeddings prepended (stub frontend output)
        x = jnp.concatenate([extra_embeds.astype(cfg.cdtype()), x], axis=1)
    return ctx.constrain(x, ("batch", "seq", "act_embed"))


def _logits(params, cfg: ModelConfig, x, ctx):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params.get("unembed", params["embedding"])
    logits = unembed(x, table)
    return ctx.constrain(logits, ("batch", "seq", "vocab"))


def forward(params, cfg: ModelConfig, tokens, ctx: ParallelContext,
            extra_embeds=None, return_aux: bool = False):
    """Full-sequence forward (training). tokens [B, T]; extra_embeds
    [B, P, d] (VLM patch stubs / audio handled by encdec module)."""
    x = _embed_inputs(params, cfg, tokens, extra_embeds, ctx)
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :]
    x, _, aux, _ = _trunk(params, cfg, x, positions, ctx)
    logits = _logits(params, cfg, x, ctx)
    if return_aux:
        return logits, aux
    return logits


def prefill(params, cfg: ModelConfig, tokens, cache, ctx: ParallelContext,
            extra_embeds=None, last_only: bool = False, cache_offset=0,
            pages=None, last_index=None):
    """Process the prompt (or one chunk of it), filling caches. Returns
    (logits, cache).

    last_only=True unembeds only the final position ([B, 1, V]) — the
    serving path needs just the next-token distribution, and unembedding
    all S positions against a 100k+ vocab dominates prefill compute
    (2·B·S·d·V flops) for no consumer.

    Chunked prefill: `cache_offset` (scalar, may be traced) is the absolute
    position of tokens[:, 0] — call repeatedly with consecutive chunks to
    fill a long prompt without materializing its full attention. `pages`
    [B, M] routes cache writes/reads through a page table (block-paged
    serving backend). `last_index` ([B] or scalar, may be traced) unembeds
    that position instead of -1, so a right-padded final chunk still yields
    the true last-prompt-token logits.
    """
    x = _embed_inputs(params, cfg, tokens, extra_embeds, ctx)
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :] + cache_offset
    x, new_cache, _, _ = _trunk(params, cfg, x, positions, ctx, cache=cache,
                                cache_offset=cache_offset, pages=pages)
    if last_index is not None:
        idx = jnp.broadcast_to(jnp.asarray(last_index), (B,))
        x = x[jnp.arange(B), idx][:, None, :]
    elif last_only:
        x = x[:, -1:, :]
    return _logits(params, cfg, x, ctx), new_cache


def decode_step(params, cfg: ModelConfig, token, position, cache,
                ctx: ParallelContext, pages=None, paged_kernel=None,
                row_mask=None, with_stats: bool = False):
    """One-token decode. token [B] or [B,1]; position scalar OR int vector
    [B] of per-row decode depths (continuous batching over a slot pool —
    each row attends/writes at its own position). `pages` [B, M] routes
    the per-row cache access through a page table (block-paged backend;
    requires vector positions); `paged_kernel` picks the Pallas paged
    flash-decode kernels over the XLA gather fallback (None = backend
    default). `row_mask` [B] marks live rows: held experts route only
    those. Returns (logits [B, V], cache), and with `with_stats` also the
    step's per-layer counters (`_trunk`)."""
    if token.ndim == 1:
        token = token[:, None]
    x = _embed_inputs(params, cfg, token, None, ctx)
    pos = jnp.asarray(position)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((1, 1), position)
    x, new_cache, _, stats = _trunk(params, cfg, x, positions, ctx,
                                    cache=cache, decode=True,
                                    position=position, pages=pages,
                                    paged_kernel=paged_kernel,
                                    row_mask=row_mask)
    logits = _logits(params, cfg, x, ctx)
    if with_stats:
        return logits[:, 0, :], new_cache, stats
    return logits[:, 0, :], new_cache
