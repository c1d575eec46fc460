"""The dense decoder with learned Q/K/V biases, the Qwen1.5 block, which
the program serves through `ModelConfig.qkv_bias`: an architecture
module written as an added file alone. It takes the dense module's tier,
program layout and counts, adds three leaves per layer, and has its own
reference layer. The biases add no matmul FLOPs and no kernel bytes, so
the counts are the dense ones."""
from __future__ import annotations

import dataclasses
import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

import model
from model import _q, _rms, _rope

dense = model.load_arch(pathlib.Path(__file__).resolve().parents[1], "dense")

# the test that plants a reference without the biases turns this off
REFERENCE_BIASES = True
BIAS_STD = 0.5
BIASES = ("bq", "bk", "bv")
LAYER_LEAVES = dense.LAYER_LEAVES + BIASES


class Tier(dense.Tier):
    def leaf_shape(self, leaf: str) -> tuple:
        if leaf in BIASES:
            heads = self.n_heads if leaf == "bq" else self.n_kv_heads
            return (heads, self.head_dim)
        return super().leaf_shape(leaf)

    def leaf_std(self, leaf: str) -> float:
        return BIAS_STD if leaf in BIASES else super().leaf_std(leaf)

    @property
    def n_params(self) -> int:
        return model.count_params(self, LAYER_LEAVES)


def tier(entry: dict) -> Tier:
    return Tier(**{f.name: entry[f.name] for f in dataclasses.fields(Tier)})


def make_weights(t: Tier, key, dtype):
    return model.stacked_weights(t, key, dtype, LAYER_LEAVES)


def program(t: Tier, weights, dtype: str):
    cfg, params = dense.program(t, weights, dtype)
    params["blocks"]["dense"]["attn"].update({k: weights[k] for k in BIASES})
    return cfg.replace(qkv_bias=True), params


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(t: Tier, w, x, quant):
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        pos = jnp.arange(T)
        h = _q(_rms(x, w["norm1"], t.norm_eps), -1, quant)
        q = jnp.einsum("td,dhk->thk", h, _q(w["wq"], 0, quant))
        k = jnp.einsum("td,dhk->thk", h, _q(w["wk"], 0, quant))
        v = jnp.einsum("td,dhk->thk", h, _q(w["wv"], 0, quant))
        if REFERENCE_BIASES:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = _q(_rope(q, pos, t.rope_theta), -1, quant)
        k = _q(_rope(k, pos, t.rope_theta), -1, quant)
        v = _q(v, -1, quant)
        g = t.n_heads // t.n_kv_heads
        k = jnp.repeat(k, g, axis=1)
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


decode_step_flops = dense.decode_step_flops
paged_attn_calls = dense.paged_attn_calls
