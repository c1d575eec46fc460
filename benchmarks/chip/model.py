"""The benchmark's own model: what every architecture shares, and the
loader that finds each tier's architecture by name.

Architectures. Each tier of a configuration file names its architecture
under `"arch"`: a module `archs/<arch>.py` of the benchmark's own
directory, loaded by file path. A module provides these functions and
nothing else is asked of it:

* `tier(entry) -> T`: a frozen, hashable description of the tier from
  its configuration entry, with at least `name`, `n_layers`,
  `vocab_size` and `n_params`;
* `make_weights(t, key, dtype)`: every leaf, on the device, in `dtype`
  (the dtype they are served in), drawn through `leaf_key` from `key`;
* `program(t, weights, dtype_name) -> (ModelConfig, params)`: the
  weights in the program's own layout, through its public surface;
* `reference_readings(t, key, seqs, served_dtype, quants)`: the plain
  fp32 reference over each sequence, as `reference_readings` below
  returns it, with the weights drawn again from `key`;
* `decode_step_flops(t, positions)`: FLOPs of one decode step over rows
  writing the given positions;
* `paged_attn_calls(t, positions, rows) -> [(bytes, flops), ...]`: one
  entry per paged-attention kernel call that such a step makes.

Weights. Every leaf of every tier is drawn from the configuration's
weights seed through a fixed key path (seed -> tier -> leaf -> layer),
so the program's weights (made in one jitted call, in the dtype they
are served in) and the reference's (made again, layer by layer, after
the program has been freed) are the same numbers. The reference takes
nothing the program made.

Reference. Written from the equations with no cache, kernel or
batching, in float32 at `highest` matmul precision: token embedding, the
architecture's layers, a final RMSNorm and an untied unembedding.
`quant="fp8"` computes the same model with every matmul input (weights
per output channel, activations per row) rounded to float8 e4m3 after
scaling: the control that the comparison must fail.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import pathlib
import re
import sys
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1          # norm gains are 1 + N(0, 0.1^2)
GLOBAL_LEAVES = ("embedding", "unembed", "final_norm")


# ---------------------------------------------------------------------------
# architectures, found by name
# ---------------------------------------------------------------------------

def load_arch(bench_dir: pathlib.Path, name: str):
    """The module `<bench_dir>/archs/<name>.py`, loaded by file path."""
    path = pathlib.Path(bench_dir) / "archs" / f"{name}.py"
    mod_name = "arch_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the class is made
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_tiers(bench_dir: pathlib.Path, cfg: dict, cfg_file: str):
    """(modules, tiers): each tier's architecture module and its
    description. A tier that names no architecture, or one that has no
    module, or whose entry its module refuses, stops the run."""
    mods, tiers = [], []
    for i, entry in enumerate(cfg["tiers"]):
        where = f"{cfg_file}: tier {i} ({entry.get('name', '?')})"
        name = entry.get("arch")
        if not name:
            raise SystemExit(f"{where} names no architecture (\"arch\")")
        path = pathlib.Path(bench_dir) / "archs" / f"{name}.py"
        if not path.is_file():
            raise SystemExit(f"{where} names architecture {name!r}, which "
                             f"has no module {path}")
        mod = load_arch(bench_dir, name)
        try:
            tiers.append(mod.tier(entry))
        except (KeyError, TypeError, ValueError) as e:
            raise SystemExit(f"{where}: architecture {name!r} refuses the "
                             f"entry: {e!r}") from e
        mods.append(mod)
    return mods, tiers


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def tier_key(seed: int, tier: int) -> jax.Array:
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(lo))
    key = jax.random.fold_in(key, np.uint32(hi))
    return jax.random.fold_in(key, np.uint32(tier))


def leaf_key(key, leaves: Sequence[str], leaf: str, layer=None):
    """The key of one leaf: the tier's key folded with the leaf's index in
    `leaves` (GLOBAL_LEAVES first, then the architecture's layer leaves)
    and, for a layer leaf, with the layer."""
    k = jax.random.fold_in(key, tuple(leaves).index(leaf))
    return k if layer is None else jax.random.fold_in(k, layer)


def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def count_params(t, layer_leaves: Sequence[str]) -> int:
    """Parameters of a tier whose every layer holds `layer_leaves`, with
    shapes from `t.leaf_shape`."""
    def size(k):
        return int(np.prod(t.leaf_shape(k)))
    return (t.n_layers * sum(size(k) for k in layer_leaves)
            + sum(size(k) for k in GLOBAL_LEAVES))


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def stacked_weights(t, key, dtype, layer_leaves: tuple
                    ) -> Dict[str, jax.Array]:
    """All leaves of a tier whose every layer holds `layer_leaves`, on
    the device, in `dtype`; layer leaves stacked on a leading axis of
    n_layers. `t` gives each leaf's `leaf_shape` and `leaf_std`."""
    leaves = GLOBAL_LEAVES + layer_leaves
    out = {k: _draw(leaf_key(key, leaves, k), t.leaf_shape(k),
                    t.leaf_std(k), dtype) for k in GLOBAL_LEAVES}
    layers = jnp.arange(t.n_layers)
    for k in layer_leaves:
        out[k] = jax.vmap(lambda l, k=k: _draw(
            leaf_key(key, leaves, k, l), t.leaf_shape(k), t.leaf_std(k),
            dtype))(layers)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def layer_weights(t, key, served_dtype, layer_leaves: tuple, layer):
    """One layer's leaves in float32, rounded through the served dtype so
    that they equal the program's values."""
    leaves = GLOBAL_LEAVES + layer_leaves
    return {k: _draw(leaf_key(key, leaves, k, layer), t.leaf_shape(k),
                     t.leaf_std(k), served_dtype).astype(jnp.float32)
            for k in layer_leaves}


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def global_weight(t, key, served_dtype, leaf):
    return _draw(leaf_key(key, GLOBAL_LEAVES, leaf), t.leaf_shape(leaf),
                 t.leaf_std(leaf), served_dtype).astype(jnp.float32)


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


@functools.partial(jax.jit, static_argnums=(0, 5))
def _readout(norm_eps: float, final_norm, unembed, h, tokens, quant):
    """Logit statistics at selected rows h [N, d] (final rows of the
    trunk): the best logit, the logit of `tokens`, the argmax, and the
    eq.-8 negative entropy sum(p log p) of the next-token distribution."""
    with jax.default_matmul_precision("highest"):
        x = _q(_rms(h, final_norm, norm_eps), -1, quant)
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


def reference_readings(t, key, seqs: Sequence[Sequence_], served_dtype,
                       quants: Sequence[Optional[str]],
                       layer_leaves: tuple, layer: Callable,
                       rows: int = 256, pad: int = 256
                       ) -> List[Dict[str, List[np.ndarray]]]:
    """Run a reference over each sequence (prompt + its tokens but the
    last), padded at the end to a multiple of `pad` positions (causal, so
    the pad changes nothing before it), layer by layer with weights drawn
    on the fly: `layer(i, w, x, quant)` is layer i over one sequence
    x [T, d], with w its `layer_leaves` in float32. `t` gives `n_layers`,
    `norm_eps` and the leaves' shapes. For each `quant` in `quants`
    returns per-sequence arrays over the emitted tokens' positions (at
    most `rows`): best logit, the emitted token's logit, the argmax, the
    fp32 run's logit of that argmax, and the negative entropy."""
    xs = {q: [] for q in quants}
    emb = global_weight(t, key, served_dtype, "embedding")
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
    for i in range(t.n_layers):
        w = layer_weights(t, key, served_dtype, layer_leaves, i)
        for q in quants:
            xs[q] = [layer(i, w, x, q) for x in xs[q]]
        del w
    fn = global_weight(t, key, served_dtype, "final_norm")
    un = global_weight(t, key, served_dtype, "unembed")
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
            r = _readout(t.norm_eps, fn, un,
                         jnp.take(x, jnp.asarray(at), axis=0),
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
