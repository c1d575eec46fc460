"""Attention blocks: GQA (optional QKV bias / sliding window / cross-attn)
and DeepSeek-style MLA (multi-head latent attention, kv_lora compression with
decoupled RoPE and weight-absorbed decode).

All functions operate on ONE layer's params (scan slices stacked trees).
Caches are dicts of arrays; decode uses dynamic_update_slice at `position`.

Cache layouts (per layer-sliced leaf):
  * dense  — [batch, max_len, ...]: one contiguous row per sequence; writes
    go to absolute position `position`, reads mask `idx <= position`.
  * paged  — [n_blocks, block_size, ...] + a page table `pages` [B, M]
    mapping each row's logical block m to a physical block id (0 is the
    shared trash block). Writes scatter to
    (pages[b, pos // block_size], pos % block_size); reads gather the
    row's blocks back into a dense [B, M*block_size, ...] view and apply
    the same per-row validity mask — so paged and dense attention compute
    identical masked softmaxes over the valid prefix. The serving pool
    instead hands every layer the leaf of its whole group,
    [L, n_blocks, block_size, F] with the features flat (GQA: F =
    KV*head_dim), and the layer's index (`layer_index`): each write and
    gather then indexes (layer, block) in place, so no layer's slice of
    the pool is ever copied out.
  Paged mode is selected by passing `pages`; sliding-window ring caches
  cannot be paged (serving.paged_pool rejects those configs), but a
  windowed layer over a full-length paged cache can: it keeps every page
  and masks positions at or before `pos - window` (the paged kernel's
  walk starts at the window's first page).

Paged DECODE has two interchangeable implementations (same masked
softmax, pinned by tests/test_paged_kernel.py):
  * XLA fallback (default on CPU) — `gather_blocks` materializes the
    dense view, then dense attention. Callers tighten it by passing a
    page table sliced to the active block prefix (the serving engine
    buckets `ceil((max_pos + steps)/block_size)` to a power of two so
    only O(log M) shapes ever compile) — the gather then reads only
    blocks the mask can reach.
  * Pallas kernel (default on TPU; kernels/paged_attention.py) — walks
    the page table inside the kernel (GQA: each row's live pages only,
    several per step), no dense view in HBM; the single-token cache
    write is also a kernel.
  Selection: the `paged_kernel` argument when given, else the backend
  default (kernels/ops.py).

Sharding: head dims carry logical axis "heads"/"kv_heads" (→ `model`);
the output projection contracts the sharded head axis, so XLA inserts the
canonical tensor-parallel all-reduce after each attention block.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.models.common import (ParamFactory, apply_rope, apply_rope_freqs,
                                 make_causal_mask, make_sliding_mask,
                                 rms_norm)
from repro.sharding import ParallelContext

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None     # None = full causal
    causal: bool = True                      # False for encoder self-attn
    attn_chunk: Optional[int] = None         # online-softmax KV chunking
    # MLA fields (used only by the mla_* functions)
    q_lora: int = 0
    kv_lora: int = 0
    rope_dim: int = 64
    v_head_dim: int = 0


# ---------------------------------------------------------------------------
# Paged-cache primitives (shared by GQA and MLA)
# ---------------------------------------------------------------------------

def gather_blocks(leaf: jnp.ndarray, pages: jnp.ndarray,
                  layer=None) -> jnp.ndarray:
    """Gather a paged cache leaf into a dense per-row view.

    leaf  [n_blocks, block_size, ...] — physical block storage, or
          [L, n_blocks, block_size, ...] with `layer` (may be traced)
          picking one: one gather of (layer, block) pairs
    pages [B, M] int32               — per-row page table (logical -> physical)

    Returns [B, M*block_size, ...]: row b's logical sequence, blocks
    concatenated in logical order. Unmapped entries point at the trash
    block (id 0); the positions they contribute lie beyond the row's valid
    prefix and are removed by the caller's `idx <= pos` mask.

    Decode callers pass `pages` sliced to the ACTIVE block prefix
    (columns `[0, ceil((max_pos + steps)/block_size))`, bucketed) so the
    gather never reads blocks the validity mask cannot reach — the
    masked softmax over the shorter view is exactly the full-view one.
    """
    B, M = pages.shape
    flat = pages.reshape(-1)
    if layer is None:
        g = jnp.take(leaf, flat, axis=0)                 # [B*M, bs, ...]
    else:
        g = leaf[jnp.full_like(flat, layer), flat]
    return g.reshape((B, M * g.shape[1]) + g.shape[2:])


def _paged_write(leaf: jnp.ndarray, pages: jnp.ndarray, tpos: jnp.ndarray,
                 values: jnp.ndarray, layer=None) -> jnp.ndarray:
    """Scatter `values` [B, T, ...] at absolute token positions `tpos`
    ([T] shared across rows, or [B, T]) through the page table, into a
    leaf of one layer or, with `layer`, into that layer of a stacked leaf
    (one scatter of (layer, block, offset) triples; the features reshape
    to the leaf's). Positions whose logical block is unmapped (table
    entry 0) land in the trash block — callers rely on this for padded
    prefill chunks and for inactive decode rows (see engine one_step)."""
    feat = leaf.shape[2 if layer is None else 3:]
    bs = leaf.shape[1 if layer is None else 2]
    B = pages.shape[0]
    if tpos.ndim == 1:
        tpos = jnp.broadcast_to(tpos[None, :], (B, tpos.shape[0]))
    blk_idx = jnp.clip(tpos // bs, 0, pages.shape[1] - 1)   # [B, T]
    blk = jnp.take_along_axis(pages, blk_idx, axis=1)       # [B, T]
    off = tpos % bs
    values = values.reshape(tpos.shape + feat).astype(leaf.dtype)
    if layer is None:
        return leaf.at[blk, off].set(values)
    return leaf.at[jnp.full_like(blk, layer), blk, off].set(values)


# ---------------------------------------------------------------------------
# Standard GQA
# ---------------------------------------------------------------------------

def _rope_and_window(cfg: AttnConfig, layer: Optional[dict]):
    """(rope fn, window) of one layer. `layer` carries a layer's kind as
    traced values (models with per-layer attention kinds, scanned as one
    stack): "window" (a full layer's exceeds any position), "inv_freq"
    [head_dim/2] and "rope_scale"; else the config's own."""
    if layer is None:
        return (lambda t, p: apply_rope(t, p, cfg.rope_theta),
                cfg.sliding_window)
    return (lambda t, p: apply_rope_freqs(t, p, layer["inv_freq"],
                                          layer["rope_scale"]),
            layer["window"])


def _gather_heads(leaf, pages, layer_index, heads):
    """`gather_blocks` as [B, M*block_size, *heads]: a pool leaf keeps its
    features flat, the gathered view (a row's active prefix) regains the
    head axes attention needs."""
    g = gather_blocks(leaf, pages, layer_index)
    return g.reshape(g.shape[:2] + tuple(heads))


def init_gqa(pf: ParamFactory, cfg: AttnConfig, stacked: int = 0) -> dict:
    L = (stacked,) if stacked else ()
    LA = ("layers",) if stacked else ()
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": pf.param("wq", L + (d, H, hd), LA + ("embed", "heads", "head_dim"),
                       fan_in=d),
        "wk": pf.param("wk", L + (d, KV, hd), LA + ("embed", "kv_heads", "head_dim"),
                       fan_in=d),
        "wv": pf.param("wv", L + (d, KV, hd), LA + ("embed", "kv_heads", "head_dim"),
                       fan_in=d),
        "wo": pf.param("wo", L + (H, hd, d), LA + ("heads", "head_dim", "embed"),
                       fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = pf.param("bq", L + (H, hd), LA + ("heads", "head_dim"), init="zeros")
        p["bk"] = pf.param("bk", L + (KV, hd), LA + ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = pf.param("bv", L + (KV, hd), LA + ("kv_heads", "head_dim"), init="zeros")
    return p


def init_gqa_cache(cfg: AttnConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16, stacked: int = 0, abstract=False) -> dict:
    from repro.sharding import AbstractParam
    L = (stacked,) if stacked else ()
    LA = ("layers",) if stacked else ()
    shape = L + (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    axes = LA + ("batch", "cache_seq", "kv_heads", "head_dim")
    if abstract:
        return {"k": AbstractParam(shape, dtype, axes),
                "v": AbstractParam(shape, dtype, axes)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attend(q, k, v, mask, scale, ctx: ParallelContext,
            chunk: Optional[int] = None):
    """q [B,Tq,H,hd]; k,v [B,Tk,KV,hd]; mask [Tq,Tk] or [B,Tq,Tk] bool.

    If `chunk` is set and divides Tk, runs the online-softmax KV-chunked
    schedule (flash-attention dataflow at the XLA level): the [Tq, Tk]
    score tensor is never live in full — only one [Tq, chunk] tile per
    scan step. This is the XLA analogue of kernels/flash_attention.py and
    is what the TPU kernel does inside VMEM.
    """
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    qg = q.reshape(B, Tq, KV, group, hd)
    if chunk:
        while k.shape[1] % chunk:
            chunk //= 2
    if chunk and chunk >= 128 and k.shape[1] > chunk:
        return _attend_chunked(qg, k, v, mask, scale, chunk
                               ).reshape(B, Tq, H, v.shape[-1]).astype(q.dtype)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        m = mask[None, None, None, :, :]
    else:
        m = mask[:, None, None, :, :]
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, H, v.shape[-1]).astype(q.dtype)


def _attend_chunked(qg, k, v, mask, scale, chunk: int):
    """Online-softmax over KV chunks. qg [B,Tq,KV,g,hd]; returns
    [B,Tq,KV,g,hd] fp32-accumulated. Masked-out rows produce zeros."""
    B, Tq, KV, g, hd = qg.shape
    Tk = k.shape[1]
    nc = Tk // chunk
    neg = jnp.float32(-jnp.inf)

    def body(carry, i):
        m, l, acc = carry                            # [B,KV,g,Tq](x2), [B,KV,g,Tq,hd]
        ks = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, 1)
        mk = jax.lax.dynamic_slice_in_dim(mask, i * chunk, chunk, mask.ndim - 1)
        s = jnp.einsum("btkgh,bskh->bkgts", qg, ks,
                       preferred_element_type=jnp.float32) * scale
        mb = (mk[None, None, None, :, :] if mk.ndim == 2
              else mk[:, None, None, :, :])
        s = jnp.where(mb, s, neg)
        cm = s.max(-1)                               # [B,KV,g,Tq]
        nm = jnp.maximum(m, cm)
        # exp(-inf - -inf) guards: fully-masked rows stay at zero weight
        safe = jnp.isfinite(nm)
        p = jnp.where(safe[..., None], jnp.exp(s - nm[..., None]), 0.0)
        alpha = jnp.where(safe, jnp.exp(jnp.minimum(m - nm, 0.0)), 0.0)
        alpha = jnp.where(jnp.isfinite(m), alpha, 0.0)
        l = l * alpha + p.sum(-1)
        pv = jnp.einsum("bkgts,bskh->bkgth", p.astype(vs.dtype), vs,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (nm, l, acc), None

    init = (jnp.full((B, KV, g, Tq), neg, jnp.float32),
            jnp.zeros((B, KV, g, Tq), jnp.float32),
            jnp.zeros((B, KV, g, Tq, v.shape[-1]), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]     # [B,KV,g,Tq,hd]
    return out.transpose(0, 3, 1, 2, 4)


def gqa_forward(params: dict, cfg: AttnConfig, x: jnp.ndarray,
                positions: jnp.ndarray, ctx: ParallelContext,
                cache: Optional[dict] = None,
                cache_offset=0,
                pages: Optional[jnp.ndarray] = None,
                layer: Optional[dict] = None,
                layer_index=None
                ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Full-sequence self-attention (training / prefill).

    If `cache` is given, writes K/V at [cache_offset, cache_offset+T) and
    attends over the written prefix (prefill); else attends in-sequence.
    `cache_offset` may be a traced scalar (chunked prefill resumes at the
    chunk's start). With `pages` [B, M] the cache is block-paged
    ([n_blocks, block_size, ...] leaves): the chunk's K/V scatter through
    the page table and attention runs over the gathered logical view;
    with `layer_index` the leaves are the group's stacked pool and this
    layer's blocks are written and gathered in place.
    `layer` gives this layer's window and rope as traced values (see
    `_rope_and_window`).
    """
    B, T, d = x.shape
    rope, window = _rope_and_window(cfg, layer)
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = rope(q, positions)
    k = rope(k, positions)
    q = ctx.constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = ctx.constrain(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = ctx.constrain(v, ("batch", "kv_seq", "kv_heads", "head_dim"))
    scale = 1.0 / np.sqrt(cfg.head_dim)

    new_cache = None
    if pages is not None:
        # a windowed layer keeps every page of its row and masks the
        # positions at or before pos - window
        assert cache is not None
        tpos = cache_offset + jnp.arange(T)
        ck = _paged_write(cache["k"], pages, tpos, k, layer_index)
        cv = _paged_write(cache["v"], pages, tpos, v, layer_index)
        new_cache = {"k": ck, "v": cv}
        kk = _gather_heads(ck, pages, layer_index, k.shape[2:])
        vv = _gather_heads(cv, pages, layer_index, v.shape[2:])
        if window is not None:
            mask = make_sliding_mask(T, kk.shape[1], cache_offset, window)
        else:
            mask = make_causal_mask(T, kk.shape[1], cache_offset)
        out = _attend(q, kk.astype(q.dtype), vv.astype(q.dtype), mask, scale,
                      ctx, cfg.attn_chunk)
    elif cache is not None and cache["k"].shape[1] < T:
        # windowed ring-buffer cache smaller than the prompt: attend
        # IN-SEQUENCE (sliding mask) and store only the last `window`
        # tokens at their ring slots (slot = position % window).
        S = cache["k"].shape[1]
        k_last = k[:, T - S:]
        v_last = v[:, T - S:]
        shift = (T - S) % S
        ck = jnp.roll(k_last.astype(cache["k"].dtype), shift, axis=1)
        cv = jnp.roll(v_last.astype(cache["v"].dtype), shift, axis=1)
        new_cache = {"k": ck, "v": cv}
        window = cfg.sliding_window or S
        mask = make_sliding_mask(T, T, cache_offset, window)
        out = _attend(q, k, v, mask, scale, ctx, cfg.attn_chunk)
    elif cache is not None:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_offset, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_offset, 0, 0))
        new_cache = {"k": ck, "v": cv}
        S = ck.shape[1]
        if window is not None:
            mask = make_sliding_mask(T, S, cache_offset, window)
        else:
            mask = make_causal_mask(T, S, cache_offset)
        out = _attend(q, ck.astype(q.dtype), cv.astype(q.dtype), mask, scale,
                      ctx, cfg.attn_chunk)
    else:
        if not cfg.causal:
            mask = jnp.ones((T, T), bool)
        elif window is not None:
            mask = make_sliding_mask(T, T, 0, window)
        else:
            mask = make_causal_mask(T, T, 0)
        out = _attend(q, k, v, mask, scale, ctx, cfg.attn_chunk)

    y = jnp.einsum("bthk,hkd->btd", out, params["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    y = ctx.constrain(y, ("batch", "seq", "act_embed"))
    return y, new_cache


def _flash_decode_sharded(q, ck, cv, mask, scale, ctx: ParallelContext):
    """Decode attention over a sequence-sharded KV cache WITHOUT gathering
    the cache (flash-decode): each shard computes a partial
    (row-max, lse, p@v) over its local seq chunk, then psum-combines.

    Returns None when the cache's seq dim is not sharded (caller falls
    back to the dense path)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import logical_to_spec
    mesh = ctx.mesh
    cache_spec = logical_to_spec(("batch", "cache_seq", "kv_heads", None),
                                 ck.shape, mesh, ctx.rules)
    if cache_spec[1] is None or mask.ndim != 2:
        return None
    seq_axes = (cache_spec[1],) if isinstance(cache_spec[1], str) \
        else tuple(cache_spec[1])
    qspec = P(cache_spec[0], None, None, None)
    kvspec = P(cache_spec[0], cache_spec[1], cache_spec[2], None)
    mspec = P(None, cache_spec[1])

    def body(ql, kl, vl, ml):
        B, Tq, H, hd = ql.shape
        KV = kl.shape[2]
        g = H // KV
        qg = ql.reshape(B, Tq, KV, g, hd)
        s = jnp.einsum("btkgh,bskh->bkgts", qg, kl,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ml[None, None, None, :, :], s, -jnp.inf)
        m = s.max(-1)                                   # local row max
        M = jax.lax.pmax(m, seq_axes)                   # global row max
        safe = jnp.isfinite(M)
        p = jnp.where(safe[..., None], jnp.exp(s - M[..., None]), 0.0)
        l = jax.lax.psum(p.sum(-1), seq_axes)
        pv = jnp.einsum("bkgts,bskh->bkgth", p.astype(vl.dtype), vl,
                        preferred_element_type=jnp.float32)
        pv = jax.lax.psum(pv, seq_axes)
        out = pv / jnp.maximum(l, 1e-30)[..., None]
        return (out.transpose(0, 3, 1, 2, 4)
                .reshape(B, Tq, H, hd).astype(ql.dtype))

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(qspec, kvspec, kvspec, mspec),
                         out_specs=qspec, check_vma=False)(q, ck, cv, mask)


def gqa_decode(params: dict, cfg: AttnConfig, x: jnp.ndarray,
               position, cache: dict, ctx: ParallelContext,
               pages: Optional[jnp.ndarray] = None,
               paged_kernel: Optional[bool] = None,
               layer: Optional[dict] = None,
               layer_index=None
               ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x [B,1,d]; position is either a scalar int (whole
    batch at the same depth — the static serving engine) or an int vector
    [B] of per-row depths (continuous batching: each slot of the KV pool
    decodes at its own position; writes become row scatters and the
    validity mask becomes per-row).

    With `pages` [B, M] the cache is block-paged: the new K/V scatters to
    (pages[b, pos // block_size], pos % block_size) and attention runs
    over each row's valid prefix with the `idx <= pos` mask —
    token-identical to the dense path. Requires per-row positions.
    `paged_kernel` picks the Pallas paged flash-decode kernel (walks the
    page table in-kernel, no dense gather) vs the XLA gather fallback;
    None = backend default. With `layer_index` the paged leaves are the
    group's stacked pool ([L, n_blocks, block_size, KV*head_dim]) and both
    implementations write and read this layer's blocks in place.

    For sliding-window configs the cache is a ring buffer of size `window`;
    the write slot is position % window and relative order is handled by
    the positional mask below. Ring caches cannot be paged; a paged
    windowed layer masks positions at or before `pos - window`. `layer`
    gives this layer's window and rope as traced values
    (`_rope_and_window`); its cache is never a ring.
    """
    B, T, d = x.shape
    assert T == 1
    rope, window = _rope_and_window(cfg, layer)
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    pos = jnp.asarray(position)
    per_row = pos.ndim == 1                    # [B] per-slot positions
    pos_bt = pos[:, None] if per_row else pos[None, None]   # [B,1] / [1,1]
    q = rope(q, pos_bt)
    k = rope(k, pos_bt)
    scale = 1.0 / np.sqrt(cfg.head_dim)
    if pages is not None:
        assert per_row, "paged decode needs per-row positions"
        if kernel_ops.paged_kernel_enabled(paged_kernel):
            # Pallas path: in-kernel paged write + flash-decode walking
            # the page table — no dense [B, M*bs, ...] view in HBM; a
            # windowed layer's walk starts at the window's first page
            ck = kernel_ops.paged_write_token(cache["k"], pages, pos,
                                              k[:, 0], layer_index)
            cv = kernel_ops.paged_write_token(cache["v"], pages, pos,
                                              v[:, 0], layer_index)
            out = kernel_ops.paged_flash_decode_gqa(
                q, ck, cv, pages, pos, window, layer_index, scale=scale)
        else:
            # XLA fallback / parity reference: scatter + dense gather of
            # the (caller-tightened) active block prefix
            ck = _paged_write(cache["k"], pages, pos[:, None], k[:, 0:1],
                              layer_index)
            cv = _paged_write(cache["v"], pages, pos[:, None], v[:, 0:1],
                              layer_index)
            kk = _gather_heads(ck, pages, layer_index, k.shape[2:])
            vv = _gather_heads(cv, pages, layer_index, v.shape[2:])
            idx = jnp.arange(kk.shape[1])[None, :]
            mask = idx <= pos[:, None]
            if window is not None:
                mask = mask & (idx > pos[:, None] - window)
            out = _attend(q, kk.astype(q.dtype), vv.astype(q.dtype),
                          mask[:, None, :],
                          scale, ctx)
        y = jnp.einsum("bthk,hkd->btd", out, params["wo"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        return y, {"k": ck, "v": cv}
    S = cache["k"].shape[1]
    ring = cfg.sliding_window is not None and S <= cfg.sliding_window
    slot = jnp.mod(pos, S) if ring else pos
    if per_row:
        rows = jnp.arange(B)
        ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    idx = jnp.arange(S)
    if ring:
        # ring buffer: slot s holds absolute position p iff p % S == s and
        # p in (position - S, position]; every slot written so far is valid
        # once position >= S - 1. Mask = slots with abs pos > position - S.
        if per_row:
            abs_pos = pos[:, None] - jnp.mod(pos[:, None] - idx[None, :], S)
            mask = (abs_pos >= 0)[:, None, :]              # [B, 1, S]
        else:
            abs_pos = pos - jnp.mod(pos - idx, S)
            mask = (abs_pos >= 0)[None, :]                 # [1, S]
    elif per_row:
        mask = idx[None, :] <= pos[:, None]                # [B, S]
        if window is not None:
            mask = mask & (idx[None, :] > pos[:, None] - window)
        mask = mask[:, None, :]                            # [B, 1, S]
    else:
        mask = (idx <= pos)[None, :]
        if window is not None:
            # linear cache larger than the window: restrict attendance
            mask = mask & (idx > pos - window)[None, :]
    out = None
    if ctx.mesh is not None:
        out = _flash_decode_sharded(q, ck.astype(q.dtype),
                                    cv.astype(q.dtype), mask, scale, ctx)
    if out is None:
        out = _attend(q, ck.astype(q.dtype), cv.astype(q.dtype), mask, scale,
                      ctx)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec): KV computed once from encoder output
# ---------------------------------------------------------------------------

def init_cross_attn(pf: ParamFactory, cfg: AttnConfig, stacked: int = 0) -> dict:
    L = (stacked,) if stacked else ()
    LA = ("layers",) if stacked else ()
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "wq": pf.param("xwq", L + (d, H, hd), LA + ("embed", "heads", "head_dim"), fan_in=d),
        "wk": pf.param("xwk", L + (d, H, hd), LA + ("embed", "heads", "head_dim"), fan_in=d),
        "wv": pf.param("xwv", L + (d, H, hd), LA + ("embed", "heads", "head_dim"), fan_in=d),
        "wo": pf.param("xwo", L + (H, hd, d), LA + ("heads", "head_dim", "embed"),
                       fan_in=H * hd),
    }


def cross_attn_kv(params: dict, enc_out: jnp.ndarray) -> dict:
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return {"k": k, "v": v}


def cross_attn_forward(params: dict, cfg: AttnConfig, x: jnp.ndarray,
                       kv: dict, ctx: ParallelContext) -> jnp.ndarray:
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    S = kv["k"].shape[1]
    mask = jnp.ones((x.shape[1], S), bool)
    out = _attend(q, kv["k"].astype(q.dtype), kv["v"].astype(q.dtype), mask,
                  1.0 / np.sqrt(cfg.head_dim), ctx)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(pf: ParamFactory, cfg: AttnConfig, stacked: int = 0) -> dict:
    L = (stacked,) if stacked else ()
    LA = ("layers",) if stacked else ()
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.head_dim, cfg.rope_dim, cfg.v_head_dim or cfg.head_dim
    qr, kvr = cfg.q_lora, cfg.kv_lora
    p = {
        "wdq": pf.param("wdq", L + (d, qr), LA + ("embed", "q_lora"), fan_in=d),
        "q_norm": pf.param("q_norm", L + (qr,), LA + ("q_lora",), init="zeros"),
        "wuq": pf.param("wuq", L + (qr, H, dn + dr), LA + ("q_lora", "heads", "head_dim"),
                        fan_in=qr),
        "wdkv": pf.param("wdkv", L + (d, kvr), LA + ("embed", "kv_lora"), fan_in=d),
        "kv_norm": pf.param("kv_norm", L + (kvr,), LA + ("kv_lora",), init="zeros"),
        "wkr": pf.param("wkr", L + (d, dr), LA + ("embed", "head_dim"), fan_in=d),
        "wuk": pf.param("wuk", L + (kvr, H, dn), LA + ("kv_lora", "heads", "head_dim"),
                        fan_in=kvr),
        "wuv": pf.param("wuv", L + (kvr, H, dv), LA + ("kv_lora", "heads", "head_dim"),
                        fan_in=kvr),
        "wo": pf.param("wo", L + (H, dv, d), LA + ("heads", "head_dim", "embed"),
                       fan_in=H * dv),
    }
    return p


def init_mla_cache(cfg: AttnConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16, stacked: int = 0, abstract=False) -> dict:
    """MLA caches the COMPRESSED kv (kv_lora) + shared rope key — this is the
    architecture's memory win (cache is head-count independent)."""
    from repro.sharding import AbstractParam
    L = (stacked,) if stacked else ()
    LA = ("layers",) if stacked else ()
    ckv_shape = L + (batch, max_len, cfg.kv_lora)
    kr_shape = L + (batch, max_len, cfg.rope_dim)
    ckv_axes = LA + ("batch", "cache_seq", "kv_lora")
    kr_axes = LA + ("batch", "cache_seq", "head_dim")
    if abstract:
        return {"ckv": AbstractParam(ckv_shape, dtype, ckv_axes),
                "kr": AbstractParam(kr_shape, dtype, kr_axes)}
    return {"ckv": jnp.zeros(ckv_shape, dtype), "kr": jnp.zeros(kr_shape, dtype)}


def _mla_qkr(params, cfg, x, positions):
    cq = jnp.einsum("btd,dr->btr", x, params["wdq"])
    cq = rms_norm(cq, params["q_norm"])
    q = jnp.einsum("btr,rhk->bthk", cq, params["wuq"])
    dn = cfg.head_dim
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_forward(params: dict, cfg: AttnConfig, x: jnp.ndarray,
                positions: jnp.ndarray, ctx: ParallelContext,
                cache: Optional[dict] = None, cache_offset=0,
                pages: Optional[jnp.ndarray] = None, layer_index=None
                ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Training / prefill path: materializes per-head K/V (compute-friendly);
    the cache still stores only (ckv, kr). `pages` selects the block-paged
    cache layout (chunked prefill): the chunk's compressed kv / rope key
    scatter through the page table, attention gathers the logical view
    (of layer `layer_index` of a stacked pool, in place)."""
    B, T, d = x.shape
    dn, dr, dv = cfg.head_dim, cfg.rope_dim, cfg.v_head_dim or cfg.head_dim
    q_nope, q_rope = _mla_qkr(params, cfg, x, positions)
    ckv = jnp.einsum("btd,dr->btr", x, params["wdkv"])
    kr = apply_rope(jnp.einsum("btd,dk->btk", x, params["wkr"])[:, :, None, :],
                    positions, cfg.rope_theta)[:, :, 0, :]
    new_cache = None
    if pages is not None:
        assert cache is not None
        tpos = cache_offset + jnp.arange(T)
        cckv = _paged_write(cache["ckv"], pages, tpos, ckv, layer_index)
        ckr = _paged_write(cache["kr"], pages, tpos, kr, layer_index)
        new_cache = {"ckv": cckv, "kr": ckr}
        ckv_all = gather_blocks(cckv, pages, layer_index).astype(x.dtype)
        kr_all = gather_blocks(ckr, pages, layer_index).astype(x.dtype)
        S = ckv_all.shape[1]
        mask = make_causal_mask(T, S, cache_offset)
    elif cache is not None:
        cckv = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, cache_offset, 0))
        ckr = jax.lax.dynamic_update_slice(
            cache["kr"], kr.astype(cache["kr"].dtype), (0, cache_offset, 0))
        new_cache = {"ckv": cckv, "kr": ckr}
        ckv_all, kr_all = cckv.astype(x.dtype), ckr.astype(x.dtype)
        S = ckv_all.shape[1]
        mask = make_causal_mask(T, S, cache_offset)
    else:
        ckv_all, kr_all, S = ckv, kr, T
        mask = make_causal_mask(T, T, 0)
    ckv_n = rms_norm(ckv_all, params["kv_norm"])
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_n, params["wuk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv_n, params["wuv"])
    k_nope = ctx.constrain(k_nope, ("batch", "seq", "heads", "head_dim"))
    v = ctx.constrain(v, ("batch", "seq", "heads", "head_dim"))
    scale = 1.0 / np.sqrt(dn + dr)
    if cfg.attn_chunk:
        # chunked (online-softmax) path: the two-term MLA score equals one
        # GQA score over concatenated (nope || rope) head dims — the
        # [T, S] tensor is never live (same schedule as _attend_chunked).
        q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_cat = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                      k_nope.shape[:3] + (dr,))], axis=-1)
        out = _attend(q_cat, k_cat, v, mask, scale, ctx,
                      cfg.attn_chunk).astype(x.dtype)
    else:
        scores = (jnp.einsum("bthk,bshk->bhts", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bthk,bsk->bhts", q_rope, kr_all,
                               preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhts,bshk->bthk", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return ctx.constrain(y, ("batch", "seq", "act_embed")), new_cache


def mla_decode(params: dict, cfg: AttnConfig, x: jnp.ndarray,
               position, cache: dict, ctx: ParallelContext,
               pages: Optional[jnp.ndarray] = None,
               paged_kernel: Optional[bool] = None,
               layer_index=None
               ) -> Tuple[jnp.ndarray, dict]:
    """Weight-absorbed decode: scores/values computed directly against the
    compressed cache — per-step FLOPs and cache reads are O(kv_lora), not
    O(heads*head_dim). This is the TPU-friendly MLA inference form.

    `position` is a scalar or an int vector [B] of per-row depths
    (continuous batching), mirroring `gqa_decode`. `pages` [B, M] selects
    the block-paged cache layout (requires per-row positions);
    `paged_kernel` picks the Pallas paged flash-decode kernel over the
    XLA gather fallback (None = env / backend default, see ops.py);
    `layer_index` picks this layer's blocks of a stacked pool."""
    B, T, d = x.shape
    assert T == 1
    dn, dr, dv = cfg.head_dim, cfg.rope_dim, cfg.v_head_dim or cfg.head_dim
    pos = jnp.asarray(position)
    per_row = pos.ndim == 1
    pos_bt = pos[:, None] if per_row else pos[None, None]
    q_nope, q_rope = _mla_qkr(params, cfg, x, pos_bt)
    ckv_new = jnp.einsum("btd,dr->btr", x, params["wdkv"])
    kr_new = apply_rope(jnp.einsum("btd,dk->btk", x, params["wkr"])[:, :, None, :],
                        pos_bt, cfg.rope_theta)[:, :, 0, :]
    scale = 1.0 / np.sqrt(dn + dr)
    if pages is not None and kernel_ops.paged_kernel_enabled(paged_kernel):
        assert per_row, "paged decode needs per-row positions"
        cckv = kernel_ops.paged_write_token(cache["ckv"], pages, pos,
                                            ckv_new[:, 0], layer_index)
        ckr = kernel_ops.paged_write_token(cache["kr"], pages, pos,
                                           kr_new[:, 0], layer_index)
        # absorb W_uk into q; the kernel rms-norms each ckv block in
        # fp32 and returns the latent context — no dense gather
        q_abs = jnp.einsum("bthk,rhk->bthr", q_nope, params["wuk"])
        ctx_lat = kernel_ops.paged_flash_decode_mla(
            q_abs, q_rope, cckv, ckr, params["kv_norm"], pages, pos,
            layer_index, scale=scale).astype(x.dtype)
        out = jnp.einsum("bthr,rhk->bthk", ctx_lat, params["wuv"])
        y = jnp.einsum("bthk,hkd->btd", out, params["wo"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        return y, {"ckv": cckv, "kr": ckr}
    if pages is not None:
        assert per_row, "paged decode needs per-row positions"
        cckv = _paged_write(cache["ckv"], pages, pos[:, None], ckv_new,
                            layer_index)
        ckr = _paged_write(cache["kr"], pages, pos[:, None], kr_new,
                           layer_index)
        ckv_seq = gather_blocks(cckv, pages, layer_index)  # [B, M*bs, r]
        kr_seq = gather_blocks(ckr, pages, layer_index)
    elif per_row:
        rows = jnp.arange(B)
        cckv = cache["ckv"].at[rows, pos].set(
            ckv_new[:, 0].astype(cache["ckv"].dtype))
        ckr = cache["kr"].at[rows, pos].set(
            kr_new[:, 0].astype(cache["kr"].dtype))
        ckv_seq, kr_seq = cckv, ckr
    else:
        cckv = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv_new.astype(cache["ckv"].dtype), (0, pos, 0))
        ckr = jax.lax.dynamic_update_slice(
            cache["kr"], kr_new.astype(cache["kr"].dtype), (0, pos, 0))
        ckv_seq, kr_seq = cckv, ckr
    S = ckv_seq.shape[1]
    ckv_n = rms_norm(ckv_seq.astype(x.dtype), params["kv_norm"])
    # absorb W_uk into q: q_abs [B,1,H,kv_lora]
    q_abs = jnp.einsum("bthk,rhk->bthr", q_nope, params["wuk"])
    scores = (jnp.einsum("bthr,bsr->bhts", q_abs, ckv_n,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthk,bsk->bhts", q_rope, kr_seq.astype(x.dtype),
                           preferred_element_type=jnp.float32)) * scale
    if per_row:
        mask = (jnp.arange(S)[None, :] <= pos[:, None])[:, None, None, :]
    else:
        mask = (jnp.arange(S) <= pos)[None, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhts,bsr->bthr", probs.astype(x.dtype), ckv_n,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("bthr,rhk->bthk", ctx_lat, params["wuv"])
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, {"ckv": cckv, "kr": ckr}
