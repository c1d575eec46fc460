"""The benchmark's own model: seeded weights and the plain fp32 reference.

Weights. Every leaf of every tier is drawn from the configuration's
weights seed through a fixed key path (seed -> tier -> leaf -> layer),
so the program's weights (made here in one jitted call, in the dtype
they are served in) and the reference's (made again, layer by layer,
after the program has been freed) are the same numbers. The reference
takes nothing the program made.

Reference. A decoder as the configuration describes it, in float32 at
`highest` matmul precision, written from the equations with no cache,
kernel or batching: token embedding; per layer a pre-norm GQA attention
block (RMSNorm with gain 1 + w, rotary embedding on the two halves of
each head, causal softmax(q k^T / sqrt(head_dim)) v) and a pre-norm
SwiGLU MLP, both added to the residual; a final RMSNorm and an untied
unembedding. `quant="fp8"` computes the same model with every matmul
input (weights per output channel, activations per row) rounded to
float8 e4m3 after scaling: the control that the comparison must fail.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1          # norm gains are 1 + N(0, 0.1^2)
LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
                "w_down")
GLOBAL_LEAVES = ("embedding", "unembed", "final_norm")


@dataclasses.dataclass(frozen=True)
class Tier:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_json(cls, d: dict) -> "Tier":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})

    def leaf_shape(self, leaf: str) -> tuple:
        d, H, KV, hd, f, V = (self.d_model, self.n_heads, self.n_kv_heads,
                              self.head_dim, self.d_ff, self.vocab_size)
        return {"norm1": (d,), "norm2": (d,), "final_norm": (d,),
                "wq": (d, H, hd), "wk": (d, KV, hd), "wv": (d, KV, hd),
                "wo": (H, hd, d), "w_gate": (d, f), "w_up": (d, f),
                "w_down": (f, d), "embedding": (V, d),
                "unembed": (V, d)}[leaf]

    def leaf_std(self, leaf: str) -> float:
        if leaf.startswith("norm") or leaf == "final_norm":
            return NORM_STD
        fan_in = {"wo": self.n_heads * self.head_dim,
                  "w_down": self.d_ff}.get(leaf, self.d_model)
        return 1.0 / float(np.sqrt(fan_in))

    @property
    def n_params(self) -> int:
        per_layer = sum(int(np.prod(self.leaf_shape(k))) for k in LAYER_LEAVES)
        return (self.n_layers * per_layer
                + sum(int(np.prod(self.leaf_shape(k))) for k in GLOBAL_LEAVES))


def tier_key(seed: int, tier: int) -> jax.Array:
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(lo))
    key = jax.random.fold_in(key, np.uint32(hi))
    return jax.random.fold_in(key, np.uint32(tier))


def _leaf_key(key, leaf: str):
    idx = (GLOBAL_LEAVES + LAYER_LEAVES).index(leaf)
    return jax.random.fold_in(key, idx)


def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def global_leaf(t: Tier, key, leaf: str, dtype=jnp.float32):
    return _draw(_leaf_key(key, leaf), t.leaf_shape(leaf), t.leaf_std(leaf),
                 dtype)


def layer_leaf(t: Tier, key, leaf: str, layer, dtype=jnp.float32):
    k = jax.random.fold_in(_leaf_key(key, leaf), layer)
    return _draw(k, t.leaf_shape(leaf), t.leaf_std(leaf), dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def make_weights(t: Tier, key, dtype) -> Dict[str, jax.Array]:
    """All leaves of one tier, on the device, in `dtype`; layer leaves
    stacked on a leading axis of n_layers."""
    out = {k: global_leaf(t, key, k, dtype) for k in GLOBAL_LEAVES}
    layers = jnp.arange(t.n_layers)
    for k in LAYER_LEAVES:
        out[k] = jax.vmap(lambda l, k=k: layer_leaf(t, key, k, l, dtype))(
            layers)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _layer_weights(t: Tier, key, layer_dtype, layer):
    """One layer's leaves in float32, rounded through the served dtype so
    that they equal the program's values."""
    return {k: layer_leaf(t, key, k, layer, layer_dtype).astype(jnp.float32)
            for k in LAYER_LEAVES}


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _global_weights(t: Tier, key, served_dtype, leaf):
    return global_leaf(t, key, leaf, served_dtype).astype(jnp.float32)


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------

def fp8_round(x, axes):
    """Round to float8 e4m3 with one scale per slice over `axes` (the
    contraction axes of the matmul the value feeds)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _q(x, axes, quant):
    return fp8_round(x, axes) if quant == "fp8" else x


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, positions, theta):
    """x [T, heads, hd]: rotate the pair (x[i], x[i + hd/2]) by
    position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv        # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(t: Tier, w, x, quant):
    """One decoder layer over one sequence x [T, d]."""
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        pos = jnp.arange(T)
        h = _q(_rms(x, w["norm1"], t.norm_eps), -1, quant)
        q = jnp.einsum("td,dhk->thk", h, _q(w["wq"], 0, quant))
        k = jnp.einsum("td,dhk->thk", h, _q(w["wk"], 0, quant))
        v = jnp.einsum("td,dhk->thk", h, _q(w["wv"], 0, quant))
        q = _q(_rope(q, pos, t.rope_theta), -1, quant)
        k = _q(_rope(k, pos, t.rope_theta), -1, quant)
        v = _q(v, -1, quant)
        g = t.n_heads // t.n_kv_heads
        k = jnp.repeat(k, g, axis=1)                 # head h reads kv h // g
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k) / np.sqrt(t.head_dim)
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = _q(jax.nn.softmax(s, axis=-1), -1, quant)
        o = _q(jnp.einsum("hqs,shk->qhk", p, v), (-2, -1), quant)
        x = x + jnp.einsum("thk,hkd->td", o, _q(w["wo"], (0, 1), quant))
        h = _q(_rms(x, w["norm2"], t.norm_eps), -1, quant)
        a = jax.nn.silu(jnp.einsum("td,df->tf", h, _q(w["w_gate"], 0, quant)))
        b = jnp.einsum("td,df->tf", h, _q(w["w_up"], 0, quant))
        y = jnp.einsum("tf,fd->td", _q(a * b, -1, quant),
                       _q(w["w_down"], 0, quant))
        return x + y


@functools.partial(jax.jit, static_argnums=(0, 5))
def _readout(t: Tier, final_norm, unembed, h, tokens, quant):
    """Logit statistics at selected rows h [N, d] (final rows of the
    trunk): the best logit, the logit of `tokens`, the argmax, and the
    eq.-8 negative entropy sum(p log p) of the next-token distribution."""
    with jax.default_matmul_precision("highest"):
        x = _q(_rms(h, final_norm, t.norm_eps), -1, quant)
        logits = jnp.einsum("nd,vd->nv", x, _q(unembed, 1, quant))
        logp = jax.nn.log_softmax(logits, -1)
        return {"best": logits.max(-1),
                "chosen": jnp.take_along_axis(logits, tokens[:, None],
                                              1)[:, 0],
                "argmax": jnp.argmax(logits, -1).astype(jnp.int32),
                "nent": jnp.sum(jnp.exp(logp) * logp, -1),
                "logits": logits}


@dataclasses.dataclass
class Sequence_:
    """One sequence checked against the reference: the prompt, the tokens
    the program emitted after it, and which of them to read."""
    prompt: np.ndarray
    tokens: np.ndarray


def reference_readings(t: Tier, key, seqs: Sequence[Sequence_],
                       served_dtype, quants: Sequence[Optional[str]] = (None,),
                       rows: int = 256, pad: int = 256
                       ) -> List[Dict[str, List[np.ndarray]]]:
    """Run the reference over each sequence (prompt + its tokens but the
    last), padded at the end to a multiple of `pad` positions (causal, so
    the pad changes nothing before it), layer by layer with weights drawn
    on the fly. For each `quant` in `quants` returns per-sequence arrays
    over the emitted tokens' positions (at most `rows`): best logit, the
    emitted token's logit, the argmax, the fp32 run's logit of that
    argmax, and the negative entropy."""
    xs = {q: [] for q in quants}
    emb = _global_weights(t, key, served_dtype, "embedding")
    for s in seqs:
        full = np.concatenate([s.prompt, s.tokens[:-1]]).astype(np.int32)
        if s.tokens.shape[0] > rows:
            raise ValueError(f"{s.tokens.shape[0]} tokens to read, at most "
                             f"{rows}")
        ids = np.zeros(-(-full.shape[0] // pad) * pad, np.int32)
        ids[:full.shape[0]] = full
        for q in quants:
            xs[q].append(jnp.take(emb, jnp.asarray(ids), axis=0))
    del emb
    for layer in range(t.n_layers):
        w = _layer_weights(t, key, served_dtype, layer)
        for q in quants:
            xs[q] = [_layer(t, w, x, q) for x in xs[q]]
        del w
    fn = _global_weights(t, key, served_dtype, "final_norm")
    un = _global_weights(t, key, served_dtype, "unembed")
    out, fp32_logits = [], []
    for q in quants:
        per = {"best": [], "chosen": [], "argmax": [], "ref_of_argmax": [],
               "nent": []}
        for i, (s, x) in enumerate(zip(seqs, xs[q])):
            n = int(s.tokens.shape[0])
            at = np.zeros(rows, np.int32)
            at[:n] = np.arange(n) + s.prompt.shape[0] - 1
            tok = np.zeros(rows, np.int32)
            tok[:n] = s.tokens
            r = _readout(t, fn, un, jnp.take(x, jnp.asarray(at), axis=0),
                         jnp.asarray(tok), q)
            logits = r.pop("logits")
            if q is None:
                fp32_logits.append(logits)
            # the fp32 reference's logit of the token this run puts first
            # (quants[0] must be None, the fp32 run)
            per["ref_of_argmax"].append(np.asarray(jnp.take_along_axis(
                fp32_logits[i], r["argmax"][:, None], 1)[:n, 0]))
            for k, v in r.items():
                per[k].append(np.asarray(v[:n]))
        out.append(per)
    return out
