"""Step builders shared by the dry-run, the trainer and the server.

  * train_step  — Gatekeeper token-level fine-tune step (the paper's
    technique in the training path) with AdamW, for every architecture.
  * prefill_fn  — prompt processing, returns last-position logits +
    deferral confidence.
  * serve_step  — one-token decode returning (next_token, confidence);
    confidence is the paper's negative-predictive-entropy deferral signal,
    computed fused with the step (eq. 8).

The loss/entropy over huge vocabularies (kimi: 163,840) is computed with a
vocab-CHUNKED two-pass algorithm so [B, S, V] logits are never materialized
in fp32 — the XLA analogue of the fused Pallas kernel in repro/kernels.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.core.gatekeeper import GatekeeperConfig
from repro.models import encdec as encdec_lib
from repro.models import transformer as tfm
from repro.models.common import rms_norm
from repro.sharding import ParallelContext
from repro.training import optim


# ---------------------------------------------------------------------------
# Vocab-chunked fused Gatekeeper loss (two-pass logsumexp, no [B,S,V] fp32)
# ---------------------------------------------------------------------------

def chunked_gatekeeper_loss(x_final: jnp.ndarray, table: jnp.ndarray,
                            targets: jnp.ndarray, gk: GatekeeperConfig,
                            valid_mask: Optional[jnp.ndarray] = None,
                            n_chunks: int = 16):
    """Gatekeeper token loss fused with the unembedding.

    x_final: [B, S, d] final hidden states; table: [V, d]; targets [B, S].
    Pass 1: per-token max/logsumexp + argmax over vocab chunks.
    Pass 2: entropy sum + target logit over vocab chunks.
    Memory: O(B*S*V/n_chunks) transient instead of O(B*S*V) fp32.
    """
    B, S, d = x_final.shape
    V = table.shape[0]
    while V % n_chunks != 0:
        n_chunks //= 2
    Vc = V // n_chunks
    x2 = x_final.reshape(B * S, d)
    tgt = targets.reshape(B * S)
    tables = table.reshape(n_chunks, Vc, d)

    def pass1(carry, tb_idx):
        m, lse_acc, amax_val, amax_idx = carry
        tb, idx = tb_idx
        logits = jnp.einsum("td,vd->tv", x2, tb,
                            preferred_element_type=jnp.float32)
        cmax = logits.max(-1)
        cam = logits.argmax(-1)
        new_m = jnp.maximum(m, cmax)
        lse_acc = lse_acc * jnp.exp(m - new_m) + jnp.exp(
            jax.scipy.special.logsumexp(logits, axis=-1) - new_m)
        better = cmax > amax_val
        amax_val = jnp.where(better, cmax, amax_val)
        amax_idx = jnp.where(better, cam + idx * Vc, amax_idx)
        return (new_m, lse_acc, amax_val, amax_idx), None

    init = (jnp.full((B * S,), -jnp.inf, jnp.float32),
            jnp.zeros((B * S,), jnp.float32),
            jnp.full((B * S,), -jnp.inf, jnp.float32),
            jnp.zeros((B * S,), jnp.int32))
    (m, lse_acc, _amax, preds), _ = jax.lax.scan(
        pass1, init, (tables, jnp.arange(n_chunks)))
    lse = m + jnp.log(lse_acc)                     # [T]

    def pass2(carry, tb_idx):
        ent_acc, tgt_logit = carry
        tb, idx = tb_idx
        logits = jnp.einsum("td,vd->tv", x2, tb,
                            preferred_element_type=jnp.float32)
        logp = logits - lse[:, None]
        ent_acc = ent_acc - jnp.sum(jnp.exp(logp) * logp, axis=-1)
        loc = tgt - idx * Vc
        in_chunk = (loc >= 0) & (loc < Vc)
        got = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, Vc - 1)[:, None], axis=-1)[:, 0]
        tgt_logit = jnp.where(in_chunk, got, tgt_logit)
        return (ent_acc, tgt_logit), None

    (entropy, tgt_logit), _ = jax.lax.scan(
        pass2, (jnp.zeros((B * S,), jnp.float32),
                jnp.zeros((B * S,), jnp.float32)),
        (tables, jnp.arange(n_chunks)))

    ce = lse - tgt_logit                           # -log p_target
    kl = jnp.log(float(V)) - entropy               # KL(p || U)
    correct = jax.lax.stop_gradient(preds == tgt).astype(jnp.float32)
    valid = (jnp.ones_like(correct) if valid_mask is None
             else valid_mask.reshape(B * S).astype(jnp.float32))
    denom = jnp.maximum(valid.sum(), 1.0)
    l_corr = (ce * correct * valid).sum() / denom
    l_incorr = (kl * (1 - correct) * valid).sum() / denom
    loss = gk.alpha * l_corr + (1 - gk.alpha) * l_incorr
    aux = {"l_corr": l_corr, "l_incorr": l_incorr,
           "frac_correct": (correct * valid).sum() / denom,
           "mean_entropy": (entropy * valid).sum() / denom}
    return loss, aux


def fused_confidence(x_final: jnp.ndarray, table: jnp.ndarray,
                     n_chunks: int = 8,
                     ctx: Optional["ParallelContext"] = None):
    """Deferral signal at decode: (neg_entropy [T], max_prob [T], argmax [T])
    from final hidden states, vocab-chunked (eq. 7/8 fused with unembed).

    With the "unembed_d" rule set, x_final's d dim is sharded so the
    table's FSDP (d) shard is contracted in place — partial [T, Vc] logits
    psum instead of a per-chunk table all-gather."""
    if ctx is not None:
        x_final = ctx.constrain(x_final, (None, "unembed_d"))
    T, d = x_final.shape
    V = table.shape[0]
    while V % n_chunks != 0:
        n_chunks //= 2
    Vc = V // n_chunks
    tables = table.reshape(n_chunks, Vc, d)

    def pass1(carry, tb_idx):
        m, lse_acc, amax_val, amax_idx = carry
        tb, idx = tb_idx
        logits = jnp.einsum("td,vd->tv", x_final, tb,
                            preferred_element_type=jnp.float32)
        cmax = logits.max(-1)
        new_m = jnp.maximum(m, cmax)
        lse_acc = lse_acc * jnp.exp(m - new_m) + jnp.exp(
            jax.scipy.special.logsumexp(logits, axis=-1) - new_m)
        better = cmax > amax_val
        amax_val = jnp.where(better, cmax, amax_val)
        amax_idx = jnp.where(better, logits.argmax(-1) + idx * Vc, amax_idx)
        return (new_m, lse_acc, amax_val, amax_idx), None

    init = (jnp.full((T,), -jnp.inf, jnp.float32),
            jnp.zeros((T,), jnp.float32),
            jnp.full((T,), -jnp.inf, jnp.float32),
            jnp.zeros((T,), jnp.int32))
    (m, lse_acc, amax_val, amax_idx), _ = jax.lax.scan(
        pass1, init, (tables, jnp.arange(n_chunks)))
    lse = m + jnp.log(lse_acc)

    def pass2(ent_acc, tb):
        logits = jnp.einsum("td,vd->tv", x_final, tb,
                            preferred_element_type=jnp.float32)
        logp = logits - lse[:, None]
        return ent_acc - jnp.sum(jnp.exp(logp) * logp, axis=-1), None

    entropy, _ = jax.lax.scan(pass2, jnp.zeros((T,), jnp.float32), tables)
    max_prob = jnp.exp(amax_val - lse)
    return -entropy, max_prob, amax_idx


# ---------------------------------------------------------------------------
# Forward wrappers returning final hidden states (pre-unembed)
# ---------------------------------------------------------------------------

def _final_hidden(params, cfg: ModelConfig, batch, ctx: ParallelContext):
    """Run the trunk and return (x_final [B,T,d], aux, valid_mask)."""
    if cfg.family == "encdec":
        enc_out = encdec_lib.encode(params, cfg, batch["frames"], ctx)
        kv = encdec_lib.cross_kv(params, cfg, enc_out)
        from repro.models.common import embed_tokens
        x = embed_tokens(params["embedding"], batch["tokens"]).astype(cfg.cdtype())
        positions = jnp.arange(x.shape[1])[None, :]
        x, _ = encdec_lib._decoder_trunk(params, cfg, x, positions, kv, ctx)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.zeros((), jnp.float32), batch.get("loss_mask")
    extra = batch.get("patches")
    x = tfm._embed_inputs(params, cfg, batch["tokens"], extra, ctx)
    positions = jnp.arange(x.shape[1])[None, :]
    x, _, aux, _ = tfm._trunk(params, cfg, x, positions, ctx)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    mask = batch.get("loss_mask")
    if extra is not None:
        # loss only on text positions (patches prepended)
        P = extra.shape[1]
        m = jnp.concatenate([jnp.zeros((x.shape[0], P), jnp.float32),
                             jnp.ones((x.shape[0], x.shape[1] - P), jnp.float32)],
                            axis=1)
        mask = m if mask is None else mask * m
    return x, aux, mask


def _pad_targets(cfg: ModelConfig, batch, T: int):
    """targets aligned with the (possibly patch-extended) sequence."""
    tgt = batch["targets"]
    if tgt.shape[1] < T:
        pad = jnp.zeros((tgt.shape[0], T - tgt.shape[1]), tgt.dtype)
        tgt = jnp.concatenate([pad, tgt], axis=1)
    return tgt


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, ctx: ParallelContext,
                    gk: GatekeeperConfig = GatekeeperConfig(alpha=0.5),
                    opt_cfg: optim.AdamWConfig = optim.AdamWConfig(),
                    aux_weight: float = 0.01,
                    microbatches: int = 1):
    """Gatekeeper fine-tune step (paper Stage 2) usable for every arch.

    microbatches > 1 runs gradient accumulation: the global batch is
    split along dim 0 and scanned, so live activations scale with the
    microbatch — the memory-term/peak knob that composes with remat."""

    def loss_fn(params, batch):
        x, model_aux, mask = _final_hidden(params, cfg, batch, ctx)
        table = params.get("unembed", params["embedding"])
        tgt = _pad_targets(cfg, batch, x.shape[1])
        loss, aux = chunked_gatekeeper_loss(x, table, tgt, gk, mask)
        return loss + aux_weight * model_aux, aux

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            B = jax.tree.leaves(batch)[0].shape[0]
            assert B % microbatches == 0, (B, microbatches)
            mb = jax.tree.map(
                lambda a: a.reshape((microbatches, B // microbatches)
                                    + a.shape[1:]), batch)

            def acc_body(carry, microbatch):
                loss_a, aux_a, grads_a = carry
                (loss, aux), grads = grads_of(params, microbatch)
                grads_a = jax.tree.map(jnp.add, grads_a, grads)
                aux_a = jax.tree.map(jnp.add, aux_a, aux)
                return (loss_a + loss, aux_a, grads_a), None

            mb0 = jax.tree.map(lambda a: a[0], mb)
            (l_sh, a_sh), g_sh = jax.eval_shape(grads_of, params, mb0)
            zeros = lambda t: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), t)
            (loss, aux, grads), _ = jax.lax.scan(
                acc_body, (zeros(l_sh), zeros(a_sh), zeros(g_sh)), mb)
            inv = 1.0 / microbatches
            loss = loss * inv
            aux = jax.tree.map(lambda a: a * inv, aux)
            grads = jax.tree.map(lambda g: g * inv, grads)
        else:
            (loss, aux), grads = grads_of(params, batch)
        params, opt_state, om = optim.adamw_update(opt_cfg, grads, opt_state,
                                                   params)
        return params, opt_state, {**aux, **om, "loss": loss}

    return train_step


def make_prefill(cfg: ModelConfig, ctx: ParallelContext):
    def prefill_fn(params, cache, batch):
        if cfg.family == "encdec":
            logits, cache = encdec_lib.prefill(
                params, cfg, batch["frames"], batch["tokens"], cache, ctx,
                last_only=True)
        else:
            logits, cache = tfm.prefill(params, cfg, batch["tokens"], cache,
                                        ctx, batch.get("patches"),
                                        last_only=True)
        last = logits[:, -1, :].astype(jnp.float32)
        logp = jax.nn.log_softmax(last, axis=-1)
        neg_ent = jnp.sum(jnp.exp(logp) * logp, axis=-1)
        return jnp.argmax(last, axis=-1), neg_ent, cache
    return prefill_fn


def make_serve_step(cfg: ModelConfig, ctx: ParallelContext,
                    tau: float = -1.0):
    """One-token decode with the fused deferral signal. Returns
    (next_token [B], confidence [B], defer [B] bool, cache)."""

    def serve_step(params, cache, token, position):
        if cfg.family == "encdec":
            x, cache = _decode_hidden_encdec(params, cfg, token, position,
                                             cache, ctx)
        else:
            x, cache = _decode_hidden(params, cfg, token, position, cache, ctx)
        table = params.get("unembed", params["embedding"])
        neg_ent, max_prob, nxt = fused_confidence(x, table, ctx=ctx)
        defer = neg_ent < tau          # eq. (6): route to M_L
        return nxt, neg_ent, defer, cache

    return serve_step


def _decode_hidden(params, cfg, token, position, cache, ctx):
    if token.ndim == 1:
        token = token[:, None]
    x = tfm._embed_inputs(params, cfg, token, None, ctx)
    x, new_cache, _, _ = tfm._trunk(params, cfg, x, None, ctx, cache=cache,
                                    decode=True, position=position)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[:, 0, :], new_cache


def _decode_hidden_encdec(params, cfg, token, position, cache, ctx):
    if token.ndim == 1:
        token = token[:, None]
    from repro.models.common import embed_tokens
    x = embed_tokens(params["embedding"], token).astype(cfg.cdtype())
    kv = jax.tree.map(lambda a: a.astype(cfg.cdtype()), cache["cross_kv"])
    x, new_self = encdec_lib._decoder_trunk(params, cfg, x, None, kv, ctx,
                                            cache=cache["dense"], decode=True,
                                            position=position)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[:, 0, :], {"dense": new_self, "cross_kv": cache["cross_kv"]}
