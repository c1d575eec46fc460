"""Shared model components: param factory, norms, RoPE, embeddings.

Models are pure functions over nested-dict param pytrees. Initialization runs
in one of two modes through `ParamFactory`:
  * real     — allocates jnp arrays (smoke tests, CPU training),
  * abstract — returns `AbstractParam` leaves (shape/dtype/logical axes) for
               the multi-pod dry-run: no allocation, exact shardings.
"""
from __future__ import annotations

import math
import zlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import AbstractParam


class ParamFactory:
    """Creates named parameters with logical sharding axes.

    RNG handling: each parameter derives its key by folding the path hash
    into the base key, so init is order-independent and stable across
    refactors.
    """

    def __init__(self, key: jax.Array | None, dtype=jnp.float32,
                 abstract: bool = False):
        self.key = key
        self.dtype = dtype
        self.abstract = abstract
        self._path: list[str] = []

    # -- scoping ----------------------------------------------------------
    def scope(self, name: str) -> "ParamFactory":
        child = ParamFactory(self.key, self.dtype, self.abstract)
        child._path = self._path + [name]
        return child

    def _key_for(self, name: str) -> jax.Array:
        # stable across processes (builtin hash() is salted per process,
        # which made init — and every downstream metric — unreproducible)
        path = "/".join(self._path + [name]).encode()
        h = np.uint32(zlib.crc32(path) % (2**31))
        return jax.random.fold_in(self.key, h)

    # -- creators ---------------------------------------------------------
    def param(self, name: str, shape: Sequence[int],
              axes: Sequence[Optional[str]],
              init: str = "normal", scale: float = 1.0,
              fan_in: Optional[int] = None, dtype=None):
        shape = tuple(int(s) for s in shape)
        dtype = dtype or self.dtype
        if self.abstract:
            return AbstractParam(shape, dtype, tuple(axes))
        k = self._key_for(name)
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "normal":
            fi = fan_in if fan_in is not None else (shape[0] if len(shape) > 1
                                                    else shape[-1])
            std = scale / np.sqrt(max(fi, 1))
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        if init == "uniform":
            return (jax.random.uniform(k, shape, jnp.float32, -scale, scale)
                    ).astype(dtype)
        if init == "constant":
            return jnp.full(shape, scale, dtype)
        raise ValueError(f"unknown init {init}")


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def init_rms_norm(pf: ParamFactory, name: str, dim: int, stacked: int = 0):
    shape = (stacked, dim) if stacked else (dim,)
    axes = ("layers", "act_embed") if stacked else ("act_embed",)
    return pf.param(name, shape, axes, init="zeros")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)                       # [head_dim/2]


def yarn_frequencies(head_dim: int, spec) -> np.ndarray:
    """YaRN inverse frequencies [head_dim/2] (arXiv:2309.00071, as
    `transformers`' `_compute_yarn_parameters` with truncation): dimension
    i keeps its frequency where it turns more than `beta_fast` times over
    `original_max_positions`, is divided by `factor` where it turns fewer
    than `beta_slow` times, and is blended linearly in between."""
    d, base, L = head_dim, spec.theta, spec.original_max_positions

    def dim_of(rotations):
        return d * math.log(L / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    lo = max(math.floor(dim_of(spec.beta_fast)), 0)
    hi = min(math.ceil(dim_of(spec.beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    pos_freqs = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1: extrapolate (keep as is)
    inv = (1.0 / (spec.factor * pos_freqs)) * (1 - keep) + (
        1.0 / pos_freqs) * keep
    return inv.astype(np.float32)


def rope_inv_freq(head_dim: int, theta: float = 10000.0, spec=None):
    """(inverse frequencies [head_dim/2] float32, cos/sin scale) of a
    rotary embedding: `spec` (a `configs.RopeSpec`) overrides `theta`; a
    YaRN spec rescales the frequencies and scales cos and sin by its
    attention factor."""
    if spec is not None and spec.kind == "yarn":
        return yarn_frequencies(head_dim, spec), float(spec.attention_factor)
    th = theta if spec is None else spec.theta
    return (1.0 / (th ** (np.arange(0, head_dim, 2, dtype=np.float32)
                          / head_dim))).astype(np.float32), 1.0


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: [..., T, H, D]; positions: broadcastable to [..., T]."""
    return apply_rope_freqs(x, positions, rope_frequencies(x.shape[-1], theta))


def apply_rope_freqs(x: jnp.ndarray, positions: jnp.ndarray, freqs,
                     scale=1.0) -> jnp.ndarray:
    """Rotate the halves of x [..., T, H, D] by positions * freqs [D/2]
    (may be traced: per-layer rope), cos and sin scaled by `scale`."""
    d = x.shape[-1]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., T, D/2]
    cos = jnp.cos(angles)[..., None, :]                     # [..., T, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    if not (isinstance(scale, float) and scale == 1.0):
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding (vocab sharded over `model`)
# ---------------------------------------------------------------------------

def init_embedding(pf: ParamFactory, vocab: int, d_model: int):
    return pf.param("embedding", (vocab, d_model), ("vocab", "embed"),
                    init="normal", fan_in=d_model)


def embed_tokens(table: jnp.ndarray, tokens: jnp.ndarray,
                 scale_by_sqrt_dim: bool = False) -> jnp.ndarray:
    out = jnp.take(table, tokens, axis=0)
    if scale_by_sqrt_dim:
        out = out * np.sqrt(table.shape[-1])
    return out


def unembed(x: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Tied or untied unembedding: x [..., d] @ table.T -> logits [..., V]."""
    return jnp.einsum("...d,vd->...v", x, table,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def make_causal_mask(q_len: int, kv_len: int, q_offset) -> jnp.ndarray:
    """[q_len, kv_len] bool; True = attendable. q_offset = absolute position
    of q index 0 (scalar or traced)."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return kv_pos <= q_pos


def make_sliding_mask(q_len: int, kv_len: int, q_offset,
                      window: int) -> jnp.ndarray:
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos <= q_pos) & (kv_pos > q_pos - window)


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(gate) * up
