"""Mellum2 (JetBrains Mellum2-12B-A2.5B-Instruct's `config.json`): per
layer a pre-norm GQA attention block of the layer's kind and a pre-norm
sparse SwiGLU expert layer, both added to the residual.

A tier's entry holds the published config's keys (`hidden_size`,
`layer_types`, `rope_parameters`, ...), with `num_experts` the experts
this chip holds and `num_experts_published`, `first_expert` and
`expert_parallel` the deployment beside it.

Reference layer, from the published equations:

* RMSNorm with gain 1 + w.
* Attention: query head h reads KV head h // (n_heads / n_kv_heads);
  softmax(q k^T / sqrt(head_dim)) v over the causal positions, and for a
  `sliding_attention` layer only over the `sliding_window` latest of them
  (key position > query position - window; the query's own included).
* Rotary embedding on the two halves of each head, by kind: sliding
  layers `default` (theta^(-2i/hd)); full layers YaRN (arXiv:2309.00071:
  frequencies of the dimensions turning more than beta_fast times over
  the original context kept, of those turning fewer than beta_slow times
  divided by `factor`, a linear ramp between, with the rotation counts
  rounded outward; cos and sin multiplied by `attention_factor`).
* Router: softmax over all published experts of h W_router (float32),
  the top `num_experts_per_tok`, renormalised to sum 1
  (`norm_topk_prob`).
* Experts: sum over the experts held here of gate * silu(h W_gate)
  (h W_up) W_down.

Departures, each also under the configuration's `assumed`:

* The sum runs over the held experts only (this chip's share of the
  EP-8 deployment): the other chips' shares are absent, in the program
  and here alike.
* No q/k norm (the config names none) and no MTP head (not used in plain
  decoding).

Counts: what the algorithm requires. A windowed layer's paged call reads
only the window's keys and values; a decode step's expert work is
counted at the expected held assignments, top_k * n_held / n_experts per
row and layer (routing is uniform in expectation), and `moe_calls`
prices each traced expert-kernel call from the step's own counters.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import model
from model import _q, _rms

LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "router",
                "w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Tier:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                   # expert width
    vocab_size: int
    norm_eps: float
    n_experts: int              # published: the router's width
    top_k: int
    n_held: int
    first_expert: int
    kinds: Tuple[str, ...]      # per layer: "sliding_attention" | "full_attention"
    window: int
    ropes: Tuple[Tuple[str, Tuple[Tuple[str, float], ...]], ...]

    def rope(self, kind: str) -> dict:
        return dict(dict(self.ropes)[kind])

    def leaf_shape(self, leaf: str) -> tuple:
        d, H, KV, hd, f, V = (self.d_model, self.n_heads, self.n_kv_heads,
                              self.head_dim, self.d_ff, self.vocab_size)
        return {"norm1": (d,), "norm2": (d,), "final_norm": (d,),
                "wq": (d, H, hd), "wk": (d, KV, hd), "wv": (d, KV, hd),
                "wo": (H, hd, d), "router": (d, self.n_experts),
                "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
                "embedding": (V, d), "unembed": (V, d)}[leaf]

    def leaf_std(self, leaf: str) -> float:
        if leaf.startswith("norm") or leaf == "final_norm":
            return model.NORM_STD
        fan_in = {"wo": self.n_heads * self.head_dim,
                  "w_down": self.d_ff}.get(leaf, self.d_model)
        return 1.0 / float(np.sqrt(fan_in))

    @property
    def n_params(self) -> int:
        def size(k):
            n = int(np.prod(self.leaf_shape(k)))
            return n * self.n_held if k in EXPERT_LEAVES else n
        return (self.n_layers * sum(size(k) for k in LAYER_LEAVES)
                + sum(size(k) for k in model.GLOBAL_LEAVES))


def tier(entry: dict) -> Tier:
    # the program's per-kind attention and held experts (a program
    # without them cannot serve this architecture: stop at once)
    from repro.configs import AttnKind, RopeSpec      # noqa: F401
    kinds = tuple(entry["layer_types"])
    if len(kinds) != entry["num_hidden_layers"]:
        raise ValueError("layer_types must list every layer")
    if not entry["norm_topk_prob"]:
        raise ValueError("the router renormalises its top-k gates")
    ropes = tuple(sorted((k, tuple(sorted(v.items())))
                         for k, v in entry["rope_parameters"].items()))
    return Tier(
        name=entry["name"], n_layers=entry["num_hidden_layers"],
        d_model=entry["hidden_size"], n_heads=entry["num_attention_heads"],
        n_kv_heads=entry["num_key_value_heads"], head_dim=entry["head_dim"],
        d_ff=entry["moe_intermediate_size"], vocab_size=entry["vocab_size"],
        norm_eps=entry["rms_norm_eps"],
        n_experts=entry["num_experts_published"],
        top_k=entry["num_experts_per_tok"], n_held=entry["num_experts"],
        first_expert=entry["first_expert"], kinds=kinds,
        window=entry["sliding_window"], ropes=ropes)


# ---------------------------------------------------------------------------
# weights: every leaf through model.leaf_key; an expert leaf is drawn per
# expert by its published index, so every share of a layer holds the same
# numbers for an expert as the uncut layer
# ---------------------------------------------------------------------------

def _leaf(t: Tier, key, dtype, leaf: str, layer):
    k = model.leaf_key(key, model.GLOBAL_LEAVES + LAYER_LEAVES, leaf, layer)
    shape, std = t.leaf_shape(leaf), t.leaf_std(leaf)
    if leaf not in EXPERT_LEAVES:
        return model._draw(k, shape, std, dtype)
    experts = t.first_expert + jnp.arange(t.n_held)
    return jax.vmap(lambda e: model._draw(jax.random.fold_in(k, e), shape,
                                          std, dtype))(experts)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _stacked_leaf(t: Tier, key, dtype, leaf: str):
    return jax.vmap(lambda l: _leaf(t, key, dtype, leaf, l))(
        jnp.arange(t.n_layers))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _layer_weights(t: Tier, key, served_dtype, layer):
    return {k: _leaf(t, key, served_dtype, k, layer).astype(jnp.float32)
            for k in LAYER_LEAVES}


def make_weights(t: Tier, key, dtype):
    """Every leaf on the device in `dtype`, one jitted call per leaf (the
    expert leaves' float32 draws would not fit beside each other)."""
    out = {k: model.global_weight(t, key, dtype, k)
           for k in model.GLOBAL_LEAVES}
    out = {k: v.astype(dtype) for k, v in out.items()}
    for k in LAYER_LEAVES:
        out[k] = _stacked_leaf(t, key, dtype, k)
    return out


def _period(kinds: Sequence[str]) -> int:
    return next(p for p in range(1, len(kinds) + 1)
                if len(kinds) % p == 0
                and tuple(kinds) == tuple(kinds[:p]) * (len(kinds) // p))


def program(t: Tier, weights, dtype: str):
    """The program's `moe` family with per-kind attention
    (`ModelConfig.layer_kinds`, one period of the pattern) and held
    experts (`MoESpec.n_held`), holding the benchmark's stacked leaves."""
    from repro.configs import AttnKind, ModelConfig, MoESpec, RopeSpec

    def rope_spec(kind):
        r = t.rope(kind)
        if r["rope_type"] == "yarn":
            return RopeSpec(
                theta=float(r["rope_theta"]), kind="yarn",
                factor=float(r["factor"]),
                original_max_positions=int(
                    r["original_max_position_embeddings"]),
                beta_fast=float(r["beta_fast"]),
                beta_slow=float(r["beta_slow"]),
                attention_factor=float(r["attention_factor"]))
        assert r["rope_type"] == "default", r
        return RopeSpec(theta=float(r["rope_theta"]))

    period = t.kinds[:_period(t.kinds)]
    kinds = tuple(AttnKind(k, window=t.window if k.startswith("sliding")
                           else None, rope=rope_spec(k)) for k in period)
    cfg = ModelConfig(
        name=t.name, family="moe", n_layers=t.n_layers, d_model=t.d_model,
        n_heads=t.n_heads, n_kv_heads=t.n_kv_heads, head_dim=t.head_dim,
        d_ff=t.d_ff, vocab_size=t.vocab_size, norm_eps=t.norm_eps,
        rope_theta=kinds[0].rope.theta,
        moe=MoESpec(n_experts=t.n_experts, top_k=t.top_k,
                    d_ff_expert=t.d_ff, n_dense_layers=0,
                    n_held=t.n_held, first_held=t.first_expert),
        layer_kinds=kinds, param_dtype=dtype, compute_dtype=dtype)
    params = {
        "embedding": weights["embedding"],
        "final_norm": weights["final_norm"],
        "unembed": weights["unembed"],
        "blocks": {"moe": {
            "norm1": weights["norm1"],
            "attn": {k: weights[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": weights["norm2"],
            "moe": {k: weights[k] for k in ("router",) + EXPERT_LEAVES},
        }},
    }
    return cfg, params


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def inv_freq(hd: int, r: dict) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [hd/2], cos/sin factor) of one rope section of
    the published config, in float64, from the equations."""
    base = float(r["rope_theta"])
    freqs = base ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    if r["rope_type"] == "default":
        return freqs, 1.0
    L, s = float(r["original_max_position_embeddings"]), float(r["factor"])

    def dim(rot):           # the dimension that turns `rot` times over L
        return hd * math.log(L / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(dim(r["beta_fast"])), 0)
    hi = min(math.ceil(dim(r["beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return freqs * (1 - ramp) + freqs / s * ramp, float(r["attention_factor"])


def _rope(x, pos, freqs, factor):
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[:, None, :] * factor
    sin = jnp.sin(ang)[:, None, :] * factor
    hd = x.shape[-1]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 6))
def _layer(t: Tier, w, x, freqs, factor, window, quant):
    """One decoder layer over one sequence x [T, d]: rope frequencies
    [hd/2] and cos/sin factor, and the attention window (past any
    position for a full layer), of the layer's kind."""
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        pos = jnp.arange(T)
        h = _q(_rms(x, w["norm1"], t.norm_eps), -1, quant)
        q = jnp.einsum("td,dhk->thk", h, _q(w["wq"], 0, quant))
        k = jnp.einsum("td,dhk->thk", h, _q(w["wk"], 0, quant))
        v = jnp.einsum("td,dhk->thk", h, _q(w["wv"], 0, quant))
        q = _q(_rope(q, pos, freqs, factor), -1, quant)
        k = _q(_rope(k, pos, freqs, factor), -1, quant)
        v = _q(v, -1, quant)
        g = t.n_heads // t.n_kv_heads
        k = jnp.repeat(k, g, axis=1)                 # head h reads kv h // g
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k) / np.sqrt(t.head_dim)
        seen = ((pos[:, None] >= pos[None, :])
                & (pos[None, :] > pos[:, None] - window))
        s = jnp.where(seen[None], s, -jnp.inf)
        p = _q(jax.nn.softmax(s, axis=-1), -1, quant)
        o = _q(jnp.einsum("hqs,shk->qhk", p, v), (-2, -1), quant)
        x = x + jnp.einsum("thk,hkd->td", o, _q(w["wo"], (0, 1), quant))
        h = _q(_rms(x, w["norm2"], t.norm_eps), -1, quant)
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", h, _q(w["router"], 0, quant)), -1)
        top, idx = jax.lax.top_k(probs, t.top_k)
        top = top / top.sum(-1, keepdims=True)
        held = t.first_expert + jnp.arange(t.n_held)
        gate = jnp.sum(jnp.where(idx[:, :, None] == held, top[:, :, None],
                                 0.0), axis=1)              # [T, n_held]
        a = jax.nn.silu(jnp.einsum("td,edf->etf", h,
                                   _q(w["w_gate"], 1, quant)))
        b = jnp.einsum("td,edf->etf", h, _q(w["w_up"], 1, quant))
        hh = _q(a * b * gate.T[:, :, None], -1, quant)
        y = jnp.einsum("etf,efd->td", hh, _q(w["w_down"], 1, quant))
        return x + y


# what the reference leaves out, for the tests' planted faults
USE_WINDOW = True
USE_YARN = True


def _kind(t: Tier, i: int):
    """(rope frequencies, cos/sin factor, window) of layer i."""
    r = t.rope(t.kinds[i])
    if not USE_YARN:
        r = dict(r, rope_type="default")
    freqs, factor = inv_freq(t.head_dim, r)
    window = t.window if _windowed(t, i) and USE_WINDOW else 1 << 30
    return (jnp.asarray(freqs, jnp.float32), jnp.float32(factor),
            jnp.int32(window))


def reference_readings(t: Tier, key, seqs, served_dtype, quants=(None,)):
    """The fp32 reference over each sequence, layer by layer, each layer's
    weights drawn again from `key` (experts by published index).
    Sequences are padded to a multiple of 768 positions (2304 holds the
    chat mix's longest), so the layer compiles for few lengths."""
    cached = {}

    def layer(i, _, x, quant):
        if cached.get("i") != i:
            cached.clear()
            cached["i"] = i
            cached["w"] = _layer_weights(t, key, served_dtype, i)
            cached["kind"] = _kind(t, i)
        return _layer(t, cached["w"], x, *cached["kind"], quant)

    return model.reference_readings(t, key, seqs, served_dtype, quants, (),
                                     layer, pad=768)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def _windowed(t: Tier, i: int) -> bool:
    return t.kinds[i].startswith("sliding")


def _kv_tokens(t: Tier, i: int, positions: Iterable[int]) -> int:
    """Key/value positions layer i's call needs over rows writing the
    given positions: the causal prefix, or its `window` latest."""
    if _windowed(t, i):
        return sum(min(p + 1, t.window) for p in positions)
    return sum(p + 1 for p in positions)


def paged_attn_calls(t: Tier, positions: Iterable[int], rows: int
                     ) -> List[Tuple[int, int]]:
    """(bytes, FLOPs) of each paged-attention call of one decode step over
    `rows` rows writing the given positions, one call per layer: the K
    and V of every position the layer attends to (a windowed layer's
    window only) plus each row's query and output, and q.k and p.v."""
    positions = list(positions)
    out = []
    for i in range(t.n_layers):
        kv = _kv_tokens(t, i, positions)
        out.append((2 * kv * t.n_kv_heads * t.head_dim * 2
                    + 2 * rows * t.n_heads * t.head_dim * 2,
                    4 * t.n_heads * t.head_dim * kv))
    return out


def moe_calls(t: Tier, hit: Sequence[int], assign: Sequence[int]
              ) -> List[Tuple[int, int]]:
    """(bytes, FLOPs) of each expert-kernel call of one decode step, from
    its per-layer counters: the weights of every held expert that took a
    token (gate, up and down, bf16) and, per assignment, its token's
    activations in and its contribution out; 6 d f FLOPs per
    assignment."""
    d, f = t.d_model, t.d_ff
    return [(h * 3 * d * f * 2 + a * 2 * d * 2, a * 6 * d * f)
            for h, a in zip(hit, assign)]


def decode_step_flops(t: Tier, positions: Iterable[int]) -> int:
    """FLOPs of one decode step over rows writing the given positions:
    each row's token through attention, the router and, at the expected
    top_k * n_held / n_experts held assignments per layer, the held
    experts; the unembedding; and its attention to the positions each
    layer reads."""
    positions = list(positions)
    d, H, KV, hd, f = (t.d_model, t.n_heads, t.n_kv_heads, t.head_dim,
                       t.d_ff)
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    experts = t.top_k * t.n_held / t.n_experts * 3 * d * f
    per_row = 2 * (t.n_layers * (attn + d * t.n_experts + experts)
                   + d * t.vocab_size)
    score = sum(4 * H * hd * _kv_tokens(t, i, positions)
                for i in range(t.n_layers))
    return int(per_row * len(positions) + score)
