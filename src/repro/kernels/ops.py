"""Jit'd public wrappers for the Pallas kernels.

The kernels run in interpret mode on the CPU backend and compile through
Mosaic everywhere else; an explicit `interpret=` argument overrides that.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import deferral_entropy as _de
from repro.kernels import flash_attention as _fa
from repro.kernels import gatekeeper_loss as _gk
from repro.kernels import moe_gmm as _moe
from repro.kernels import paged_attention as _pa


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def paged_kernel_enabled(override: Optional[bool] = None) -> bool:
    """Should the paged decode paths use the Pallas kernels?

    An explicit `override` (engine config / function arg) wins; otherwise
    the kernels are on for TPU and off elsewhere (interpret-mode kernels
    run at Python speed, so the XLA gather path is the CPU default; pass
    True to run the kernels in interpret mode, e.g. for parity tests)."""
    if override is not None:
        return bool(override)
    return jax.default_backend() == "tpu"


def _pad_tokens(x, tb):
    T = x.shape[0]
    Tp = ((T + tb - 1) // tb) * tb
    if Tp == T:
        return x, T
    pad = [(0, Tp - T)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad), T


@partial(jax.jit, static_argnames=("alpha", "interpret", "tb", "vb", "db"))
def gatekeeper_loss_fused(x, table, targets, valid=None, *, alpha: float = 0.5,
                          interpret: Optional[bool] = None,
                          tb: int = 128, vb: int = 512, db: int = 512):
    """Scalar Gatekeeper loss + per-token aux, via the fused Pallas kernel.

    x [T, d] final hidden states; table [V, d]; targets [T]."""
    interpret = _default_interpret() if interpret is None else interpret
    xp, T = _pad_tokens(x, tb)
    tp, _ = _pad_tokens(targets, tb)
    ce, kl, corr, ent = _gk.gatekeeper_loss_tokens(
        xp, table, tp, tb=tb, vb=vb, db=db, interpret=interpret)
    ce, kl, corr, ent = (a[:T] for a in (ce, kl, corr, ent))
    v = jnp.ones((T,), jnp.float32) if valid is None else valid.astype(jnp.float32)
    denom = jnp.maximum(v.sum(), 1.0)
    l_corr = (ce * corr * v).sum() / denom
    l_incorr = (kl * (1 - corr) * v).sum() / denom
    loss = alpha * l_corr + (1 - alpha) * l_incorr
    return loss, {"ce": ce, "kl": kl, "correct": corr, "entropy": ent,
                  "l_corr": l_corr, "l_incorr": l_incorr}


@partial(jax.jit, static_argnames=("interpret", "tb", "vb"))
def deferral_signal(logits, *, interpret: Optional[bool] = None,
                    tb: int = 128, vb: int = 2048):
    """(neg_entropy, max_prob, argmax) per row of logits [T, V] (eqs. 7-8)."""
    interpret = _default_interpret() if interpret is None else interpret
    lp, T = _pad_tokens(logits, tb)
    nent, mprob, amax = _de.deferral_entropy(lp, tb=tb, vb=vb,
                                             interpret=interpret)
    return nent[:T], mprob[:T], amax[:T]


@partial(jax.jit, static_argnames=("causal", "window", "interpret", "qb", "kb"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: Optional[bool] = None,
                    qb: int = 128, kb: int = 128):
    """Block-wise online-softmax GQA attention (see flash_attention.py)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               qb=qb, kb=kb, interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_decode_gqa(q, k_pages, v_pages, tables, positions,
                           window=None, layer=None, *,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """One-token GQA attention directly against the block-paged cache
    (see paged_attention.py). q [B,1,H,hd]; k/v_pages [N, bs, KV, hd], or
    stacked `[L, N, bs, KV*hd]` with `layer` (may be traced) picking one;
    tables [B, M]; positions [B]; `window` (None, or a scalar that may
    be traced) limits each row to positions in (pos - window, pos]. No
    dense gather is materialized."""
    interpret = _default_interpret() if interpret is None else interpret
    return _pa.paged_flash_decode_gqa(q, k_pages, v_pages, tables,
                                      positions, layer, scale=scale,
                                      window=window, interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "eps", "interpret"))
def paged_flash_decode_mla(q_abs, q_rope, ckv_pages, kr_pages, kv_norm,
                           tables, positions, layer=None, *, scale: float,
                           eps: float = 1e-6,
                           interpret: Optional[bool] = None):
    """Weight-absorbed MLA decode against the paged compressed cache
    (single-layer leaves, or stacked with `layer` picking one); returns
    the latent context [B,1,H,kv_lora] (caller applies W_uv/W_o)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _pa.paged_flash_decode_mla(q_abs, q_rope, ckv_pages, kr_pages,
                                      kv_norm, tables, positions, layer,
                                      scale=scale, eps=eps,
                                      interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def moe_gmm_decode(x, gates, w_gate, w_up, w_down, layer=None, *,
                   interpret: Optional[bool] = None):
    """Decode-step SwiGLU over the held experts that received a token
    (see moe_gmm.py). x [T, d]; gates [T, n_held]; weights
    [n_held, d, f] / [n_held, f, d], or stacked over layers with `layer`
    picking one. Returns [T, d]."""
    interpret = _default_interpret() if interpret is None else interpret
    return _moe.moe_gmm_decode(x, gates, w_gate, w_up, w_down, layer,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def paged_write_token(leaf, tables, positions, values, layer=None, *,
                      interpret: Optional[bool] = None):
    """Single-token paged scatter through the page table (in-kernel
    replacement for the XLA `_paged_write` on the decode hot path); a
    leaf stacked over layers is written at block (`layer`, page)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _pa.paged_write_token(leaf, tables, positions, values, layer,
                                 interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv(q, k, v, logw, u, state0, *, chunk: int = 64,
        interpret: Optional[bool] = None):
    """RWKV6 chunked recurrence (see wkv_scan.py). The [K,V] state stays
    in VMEM across chunk steps; oracle: models/ssm.linear_attention_scan
    (mode="rwkv")."""
    from repro.kernels import wkv_scan as _wkv
    interpret = _default_interpret() if interpret is None else interpret
    return _wkv.wkv_scan(q, k, v, logw, u, state0, chunk=chunk,
                         interpret=interpret)
