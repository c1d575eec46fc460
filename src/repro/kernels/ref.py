"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These mirror repro.core math but are kept dependency-free so kernel tests
compare against a single obvious implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gatekeeper_loss_ref(x: jnp.ndarray, table: jnp.ndarray,
                        targets: jnp.ndarray, alpha: float,
                        valid: jnp.ndarray):
    """Per-token Gatekeeper terms from final hidden states.

    x [T, d], table [V, d], targets [T], valid [T] in {0,1}.
    Returns dict with per-token ce, kl, correct, and the scalar loss
    (normalized by sum(valid), paper eqs. 1-5).
    """
    logits = jnp.einsum("td,vd->tv", x, table).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    V = table.shape[0]
    ce = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    ent = -(jnp.exp(logp) * logp).sum(-1)
    kl = jnp.log(float(V)) - ent
    correct = (logits.argmax(-1) == targets).astype(jnp.float32)
    v = valid.astype(jnp.float32)
    denom = jnp.maximum(v.sum(), 1.0)
    l_corr = (ce * correct * v).sum() / denom
    l_incorr = (kl * (1 - correct) * v).sum() / denom
    loss = alpha * l_corr + (1 - alpha) * l_incorr
    return {"ce": ce, "kl": kl, "correct": correct, "entropy": ent,
            "loss": loss, "l_corr": l_corr, "l_incorr": l_incorr}


def deferral_entropy_ref(logits: jnp.ndarray):
    """(neg_entropy [T], max_prob [T], argmax [T]) from logits [T, V]."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    neg_ent = (p * logp).sum(-1)
    return neg_ent, p.max(-1), jnp.argmax(logits, -1).astype(jnp.int32)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int = 0,
                        scale: float | None = None):
    """Plain softmax attention. q [B,T,H,hd]; k,v [B,S,KV,hd] (GQA)."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    scale = scale or 1.0 / np.sqrt(hd)
    g = H // KV
    qg = q.reshape(B, T, KV, g, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(T)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, T, H, hd)


def _rope_ref(x, pos, theta: float, spec=None):
    """Rotary embedding from its equations: pair (x[i], x[i + hd/2]) turns
    by pos * f_i; YaRN (arXiv:2309.00071) blends f_i and f_i / factor by
    a ramp over the dimensions that turn between beta_slow and beta_fast
    times over the original context, and scales cos and sin."""
    hd = x.shape[-1]
    base = theta if spec is None else spec.theta
    f = base ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    c = 1.0
    if spec is not None and spec.kind == "yarn":
        L = spec.original_max_positions
        dim = lambda rot: hd * np.log(L / (rot * 2 * np.pi)) / (      # noqa
            2 * np.log(base))
        lo = max(np.floor(dim(spec.beta_fast)), 0)
        hi = min(np.ceil(dim(spec.beta_slow)), hd - 1)
        r = np.clip((np.arange(hd // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
        f = f * (1 - r) + f / spec.factor * r
        c = spec.attention_factor
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(f, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None] * c, jnp.sin(ang)[:, None] * c
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lm_forward_ref(params: dict, cfg, tokens: jnp.ndarray) -> jnp.ndarray:
    """Plain float32 forward of a decoder whose layers are one stacked
    group (`blocks["dense"]` or `blocks["moe"]`), with per-layer attention
    kinds (`cfg.layer_kinds`: window and rope per kind) and, for MoE,
    experts held as `cfg.moe` says (routing over every expert, softmax,
    top-k, renormalised gates; only the held experts' terms summed).
    tokens [T] -> logits [T, V]. No cache, kernel or batching."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)                   # noqa
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        pos = jnp.arange(T)
        kind = "moe" if "moe" in params["blocks"] else "dense"
        blk = jax.tree.map(f32, params["blocks"][kind])

        def rms(x, w):
            return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                + cfg.norm_eps) * (1 + w)

        x = f32(params["embedding"])[tokens]
        for i in range(cfg.n_layers):
            w = jax.tree.map(lambda a: a[i], blk)
            k_ = (cfg.layer_kinds[i % len(cfg.layer_kinds)]
                  if cfg.layer_kinds else None)
            window = k_.window if k_ is not None else cfg.sliding_window
            spec = k_.rope if k_ is not None else None
            h = rms(x, w["norm1"])
            a = w["attn"]
            q = _rope_ref(jnp.einsum("td,dhk->thk", h, a["wq"]), pos,
                          cfg.rope_theta, spec)
            k = _rope_ref(jnp.einsum("td,dhk->thk", h, a["wk"]), pos,
                          cfg.rope_theta, spec)
            v = jnp.einsum("td,dhk->thk", h, a["wv"])
            g = cfg.n_heads // cfg.n_kv_heads
            k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
            s = jnp.einsum("qhk,shk->hqs", q, k) / np.sqrt(cfg.head_dim)
            seen = pos[:, None] >= pos[None, :]
            if window:
                seen &= pos[None, :] > pos[:, None] - window
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            x = x + jnp.einsum("hqs,shk,hkd->qd", p, v, a["wo"])
            h = rms(x, w["norm2"])
            if kind == "dense":
                m = w["mlp"]
                x = x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])
                         ) @ m["w_down"]
                continue
            m, mc = w["moe"], cfg.moe
            probs = jax.nn.softmax(h @ m["router"], -1)
            top, idx = jax.lax.top_k(probs, mc.top_k)
            top = top / top.sum(-1, keepdims=True)
            first = mc.first_held if mc.n_held is not None else 0
            for e in range(m["w_gate"].shape[0]):
                gate = jnp.sum(jnp.where(idx == first + e, top, 0.0), -1)
                y = (jax.nn.silu(h @ m["w_gate"][e]) * (h @ m["w_up"][e])
                     ) @ m["w_down"][e]
                x = x + gate[:, None] * y
        x = rms(x, f32(params["final_norm"]))
        table = params.get("unembed", params["embedding"])
        return x @ f32(table).T
