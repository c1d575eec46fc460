"""Grouped SwiGLU matmul over the experts a device holds, for decode.

At decode a step holds a few rows (1-16), so an expert layer is bound by
the reads of its experts' weights: `3 * d * f` of them per expert. The
kernel reads only the experts that received at least one of the step's
tokens and skips the rest. The grid is `(n_held, f // tf)`: grid step
`(i, j)` computes tile `j` of the `i`-th HIT expert (`ids[i]`), for every
row at once, scaled per row by that expert's gate (zero for a row not
routed to it), and adds `h @ W_down` into an fp32 accumulator that stays
in VMEM for the whole call. Steps past the hit count map to the block
the last real step fetched, so the pipeline issues no copy for them and
the kernel computes nothing there (a layer whose held experts took no
token reads one block and computes nothing). A layer scan hands the
kernel every layer's experts stacked, and the layer as a scalar: the
weights are read in place, with no per-layer slice copied first. Weights stream `tf` columns at a time
(the whole expert width where a double-buffered expert fits the VMEM
budget); the rows, the gates and the accumulator are resident.

Oracle: `models.ffn.held_expert_ffn` (the XLA path over every held
expert) and the sorted `lax.ragged_dot` path (tests/test_mellum.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "moe_gmm_decode"
# double-buffered weight tiles (gate, up and down) of one grid step
_VMEM_BUDGET = 24 * 1024 * 1024


def ffn_tile(d: int, f: int, itemsize: int) -> int:
    """Columns of the expert width per grid step: the largest multiple of
    128 dividing f (or f itself) whose double-buffered gate, up and down
    tiles fit the VMEM budget."""
    cands = [f] + [t for t in range(f - f % 128, 0, -128)
                   if t and f % t == 0]
    for t in cands:
        if 2 * 3 * d * t * itemsize <= _VMEM_BUDGET:
            return t
    return cands[-1]


def _kernel(ids_ref, nhit_ref, layer_ref, x_ref, g_ref, wg_ref, wu_ref,
            wd_ref, o_ref, acc_ref, *, n_f: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nhit_ref[0])
    def _():
        x = x_ref[...]
        g = g_ref[...]                                   # [Tp, n_held]
        col = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) == ids_ref[i]
        gate = jnp.sum(jnp.where(col, g, 0.0), axis=1, keepdims=True)
        a = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        b = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(a) * b * gate).astype(wd_ref.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when((i == pl.num_programs(0) - 1) & (j == n_f - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm_decode(x: jnp.ndarray, gates: jnp.ndarray, w_gate: jnp.ndarray,
                   w_up: jnp.ndarray, w_down: jnp.ndarray, layer=None, *,
                   interpret: bool = False) -> jnp.ndarray:
    """x [T, d]; gates [T, n_held] fp32 (a row's gate for each held
    expert, 0 where the row is not routed to it); w_gate/w_up
    [n_held, d, f], w_down [n_held, f, d], or all three stacked over
    layers ([L, n_held, ...]) with `layer` (a scalar, may be traced)
    picking one. Returns [T, d] in x's dtype: sum over held experts e of
    gates[:, e] * SwiGLU_e(x). Experts with no gated row are neither read
    nor computed. Stacked weights are read in place: a layer scan that
    passed each layer's slice would copy every held expert each step."""
    if w_gate.ndim == 3:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    T, d = x.shape
    _, E, _, f = w_gate.shape
    tf = ffn_tile(d, f, jnp.dtype(w_gate.dtype).itemsize)
    n_f = f // tf
    Tp = -(-T // 16) * 16
    xp = jnp.zeros((Tp, d), w_gate.dtype).at[:T].set(x.astype(w_gate.dtype))
    gp = jnp.zeros((Tp, E), jnp.float32).at[:T].set(
        gates.astype(jnp.float32))
    hit = jnp.any(gp != 0, axis=0)
    n_hit = jnp.sum(hit).astype(jnp.int32)
    # the hit experts first, in order; later steps repeat the last one
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(E) < n_hit, order,
                    order[jnp.maximum(n_hit - 1, 0)])

    def tile(i, j, nhit):
        # past the hit count: the block the last real step fetched
        return jnp.where(i < nhit[0], j, n_f - 1)

    whole = lambda i, j, ids, nhit, lyr: (0, 0)          # noqa: E731
    sq = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(E, n_f),
        in_specs=[
            pl.BlockSpec((Tp, d), whole),
            pl.BlockSpec((Tp, E), whole),
            pl.BlockSpec((sq, sq, d, tf), lambda i, j, ids, nhit, lyr: (
                lyr[0], ids[i], 0, tile(i, j, nhit))),
            pl.BlockSpec((sq, sq, d, tf), lambda i, j, ids, nhit, lyr: (
                lyr[0], ids[i], 0, tile(i, j, nhit))),
            pl.BlockSpec((sq, sq, tf, d), lambda i, j, ids, nhit, lyr: (
                lyr[0], ids[i], tile(i, j, nhit), 0)),
        ],
        out_specs=pl.BlockSpec((Tp, d), whole),
        scratch_shapes=[pltpu.VMEM((Tp, d), jnp.float32)],
    )
    isz = jnp.dtype(w_gate.dtype).itemsize
    vmem = 2 * 3 * d * tf * isz + 4 * Tp * d * 4 + (8 << 20)
    out = pl.pallas_call(
        functools.partial(_kernel, n_f=n_f), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(max(vmem, 16 << 20))),
        interpret=interpret, name=NAME,
    )(ids, n_hit[None], jnp.asarray(layer, jnp.int32)[None], xp, gp,
      w_gate, w_up, w_down)
    return out[:T]
