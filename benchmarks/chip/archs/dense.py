"""Dense decoder: per layer a pre-norm GQA attention block and a pre-norm
SwiGLU MLP, both added to the residual (internlm2, and the stand-in top
tier `repro.launch.serve.build_ladder` derives from it).

Reference layer: RMSNorm with gain 1 + w, rotary embedding on the two
halves of each head, full causal softmax(q k^T / sqrt(head_dim)) v with
query head h reading KV head h // (n_heads / n_kv_heads), then
silu(h W_gate) * (h W_up) W_down.

Counts: what the algorithm requires, not what a kernel happens to move:
no padding rows, no blocks past a row's valid prefix, no recomputation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import model
from model import _q, _rms, _rope

LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
                "w_down")


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
            return model.NORM_STD
        fan_in = {"wo": self.n_heads * self.head_dim,
                  "w_down": self.d_ff}.get(leaf, self.d_model)
        return 1.0 / float(np.sqrt(fan_in))

    @property
    def n_params(self) -> int:
        return model.count_params(self, LAYER_LEAVES)


def tier(entry: dict) -> Tier:
    return Tier(**{f.name: entry[f.name] for f in dataclasses.fields(Tier)})


def make_weights(t: Tier, key, dtype):
    return model.stacked_weights(t, key, dtype, LAYER_LEAVES)


def program(t: Tier, weights, dtype: str):
    """The program's dense family (`repro.models.transformer.init_params`
    layout) holding the benchmark's stacked leaves."""
    from repro.configs import ModelConfig
    cfg = ModelConfig(name=t.name, family="dense", n_layers=t.n_layers,
                      d_model=t.d_model, n_heads=t.n_heads,
                      n_kv_heads=t.n_kv_heads, head_dim=t.head_dim,
                      d_ff=t.d_ff, vocab_size=t.vocab_size,
                      rope_theta=t.rope_theta, norm_eps=t.norm_eps,
                      param_dtype=dtype, compute_dtype=dtype)
    params = {
        "embedding": weights["embedding"],
        "final_norm": weights["final_norm"],
        "unembed": weights["unembed"],
        "blocks": {"dense": {
            "norm1": weights["norm1"],
            "attn": {k: weights[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": weights["norm2"],
            "mlp": {k: weights[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }
    return cfg, params


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

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


def reference_readings(t: Tier, key, seqs, served_dtype, quants=(None,)):
    return model.reference_readings(
        t, key, seqs, served_dtype, quants, LAYER_LEAVES,
        lambda _, w, x, q: _layer(t, w, x, q))


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def paged_attn_bytes(kv_tokens: int, rows: int, t: Tier,
                     itemsize: int = 2) -> int:
    """Bytes one paged GQA decode call must move for one layer: the K and
    V of every valid position of every active row (`kv_tokens` summed over
    rows), plus each active row's query and output."""
    kv = 2 * kv_tokens * t.n_kv_heads * t.head_dim * itemsize
    qo = 2 * rows * t.n_heads * t.head_dim * itemsize
    return kv + qo


def paged_attn_flops(kv_tokens: int, t: Tier) -> int:
    """FLOPs of one paged GQA decode call for one layer: q.k and p.v, two
    multiply-adds per head dimension, per head, per valid position."""
    return 4 * t.n_heads * t.head_dim * kv_tokens


def paged_attn_calls(t: Tier, positions: Iterable[int], rows: int
                     ) -> List[Tuple[int, int]]:
    """(bytes, FLOPs) of each paged-attention call of one decode step over
    `rows` rows writing the given positions: one call per layer, each
    over every row's full causal prefix."""
    kv = sum(p + 1 for p in positions)
    call = (paged_attn_bytes(kv, rows, t), paged_attn_flops(kv, t))
    return [call] * t.n_layers


def matmul_params_per_token(t: Tier) -> int:
    """Weights one token multiplies through in the trunk (all layers, no
    embedding lookup, no unembedding)."""
    d, H, KV, hd, f = (t.d_model, t.n_heads, t.n_kv_heads, t.head_dim,
                       t.d_ff)
    return t.n_layers * (d * H * hd + 2 * d * KV * hd + H * hd * d
                         + 3 * d * f)


def attn_flops_at(t: Tier, positions: Iterable[int]) -> int:
    """Attention FLOPs of the tokens at the given positions, each
    attending causally to itself and every earlier position."""
    return 4 * t.n_layers * t.n_heads * t.head_dim * sum(
        p + 1 for p in positions)


def decode_step_flops(t: Tier, positions: Iterable[int]) -> int:
    """FLOPs of one decode step over rows writing the given positions:
    each row's token through the trunk and the unembedding, and its
    attention to itself and every earlier position."""
    positions = list(positions)
    per_row = 2 * (matmul_params_per_token(t) + t.d_model * t.vocab_size)
    return per_row * len(positions) + attn_flops_at(t, positions)


def forward_flops_full_square(t: Tier, T: int) -> Tuple[int, int]:
    """(matmul, attention) FLOPs of one dense forward over T tokens that
    computes the full T x T score matrix and unembeds every position, as
    `repro.models.transformer.forward` does: what XLA's cost analysis
    counts, for the tests."""
    mm = 2 * (matmul_params_per_token(t) + t.d_model * t.vocab_size) * T
    att = 4 * t.n_layers * t.n_heads * t.head_dim * T * T
    return mm, att
