"""Feed-forward blocks: dense (SwiGLU / GELU) and Mixture-of-Experts.

MoE design (TPU-adapted, MaxText-style, FLOP-honest):
  * Experts are sharded over the `model` mesh axis (expert parallelism).
  * Training/prefill ("scatter" path): activations are resharded so tokens
    are split over BOTH (data, model); each device routes its local tokens,
    packs per-destination capacity buffers, exchanges them with
    `lax.all_to_all` over the model axis, runs a sort + `lax.ragged_dot`
    grouped matmul over its local experts, and reverses the exchange.
    Compute and communication both scale with *active* (top-k) FLOPs — no
    GShard dense-dispatch einsum (which would be ~100x the useful FLOPs at
    384 experts).
  * Decode ("local" path): tokens are few; each model shard gathers only the
    assignments that hit its local experts into a small capacity buffer,
    computes, and the result is psum-combined over the model axis.
  * Single-device path (no mesh): same sort + ragged_dot math without
    collectives — used by smoke tests and CPU training, and as the oracle
    for the distributed paths.

Capacity overflow drops assignments (standard GShard semantics, gates NOT
renormalized); the router aux load-balance loss keeps overflow rare.

Held experts (`MoEConfig.n_held`, one device's share of an expert-parallel
deployment served with no exchange): the layer routes every token over
all `n_experts` and computes only the contributions of the experts it
holds, with no capacity and so no drop. Its output is one share of the
layer: the shares of all devices sum to the whole layer. The decode step
runs the Pallas grouped matmul (`kernels/moe_gmm.py`, op
`moe_gmm_decode`), which reads only the held experts that received a
token; prefill and the XLA fallback compute every held expert densely.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ParamFactory, swiglu
from repro.sharding import ParallelContext


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    d_model: int
    d_ff: int
    activation: str = "swiglu"     # "swiglu" | "gelu"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                      # per-expert hidden dim
    n_experts: int
    top_k: int
    n_shared_experts: int = 0
    shared_d_ff: Optional[int] = None   # defaults to d_ff * n_shared
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    normalize_gates: bool = True   # renormalize top-k gates to sum 1
    # experts this device holds, [first_held, first_held + n_held); None =
    # every expert (the paths above). Routing always runs over n_experts.
    n_held: Optional[int] = None
    first_held: int = 0


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------

def init_mlp(pf: ParamFactory, cfg: MLPConfig, stacked: int = 0) -> dict:
    L = (stacked,) if stacked else ()
    LA = ("layers",) if stacked else ()
    d, f = cfg.d_model, cfg.d_ff
    p = {"w_up": pf.param("w_up", L + (d, f), LA + ("embed", "ffn"), fan_in=d),
         "w_down": pf.param("w_down", L + (f, d), LA + ("ffn", "embed"), fan_in=f)}
    if cfg.activation == "swiglu":
        p["w_gate"] = pf.param("w_gate", L + (d, f), LA + ("embed", "ffn"), fan_in=d)
    else:
        p["b_up"] = pf.param("b_up", L + (f,), LA + ("ffn",), init="zeros")
        p["b_down"] = pf.param("b_down", L + (d,), LA + ("act_embed",), init="zeros")
    return p


def mlp_forward(params: dict, cfg: MLPConfig, x: jnp.ndarray,
                ctx: ParallelContext) -> jnp.ndarray:
    if cfg.activation == "swiglu":
        h = swiglu(jnp.einsum("btd,df->btf", x, params["w_gate"]),
                   jnp.einsum("btd,df->btf", x, params["w_up"]))
    else:
        h = jax.nn.gelu(jnp.einsum("btd,df->btf", x, params["w_up"])
                        + params["b_up"])
    h = ctx.constrain(h, ("batch", "seq", "ffn"))
    y = jnp.einsum("btf,fd->btd", h, params["w_down"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.activation != "swiglu":
        y = y + params["b_down"].astype(y.dtype)
    return ctx.constrain(y, ("batch", "seq", "act_embed"))


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def init_moe(pf: ParamFactory, cfg: MoEConfig, stacked: int = 0) -> dict:
    L = (stacked,) if stacked else ()
    LA = ("layers",) if stacked else ()
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    Eh = E if cfg.n_held is None else cfg.n_held    # expert weights held
    p = {
        "router": pf.param("router", L + (d, E), LA + ("embed", "experts"),
                           fan_in=d, dtype=jnp.float32),
        # dedicated logical axes so the expert matrices' FSDP/TP dims can be
        # re-ruled independently of dense params (hillclimb: "gather tokens,
        # not weights" at decode). Defaults reproduce the old
        # embed->data / ffn->() sharding exactly.
        "w_gate": pf.param("we_gate", L + (Eh, d, f),
                           LA + ("experts", "expert_embed", "expert_ffn"),
                           fan_in=d),
        "w_up": pf.param("we_up", L + (Eh, d, f),
                         LA + ("experts", "expert_embed", "expert_ffn"),
                         fan_in=d),
        "w_down": pf.param("we_down", L + (Eh, f, d),
                           LA + ("experts", "expert_ffn", "expert_embed"),
                           fan_in=f),
    }
    if cfg.n_shared_experts > 0:
        sf = cfg.shared_d_ff or cfg.d_ff * cfg.n_shared_experts
        shared_cfg = MLPConfig(cfg.d_model, sf, "swiglu")
        p["shared"] = init_mlp(pf.scope("shared"), shared_cfg, stacked)
    return p


def _route(router_w: jnp.ndarray, x2d: jnp.ndarray, cfg: MoEConfig):
    """Router: returns (gates [T,k] fp32, expert_idx [T,k] int32, aux_loss)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.normalize_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balance aux: E * sum_e f_e * P_e
    E = cfg.n_experts
    counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_prob)
    return gates, idx.astype(jnp.int32), aux


def held_gates(router_w: jnp.ndarray, x2d: jnp.ndarray, cfg: MoEConfig,
               row_mask: Optional[jnp.ndarray] = None):
    """Route every token over all experts; return (gates [T, n_held] fp32
    of the held experts, 0 where a token is not routed to one, aux loss).
    Rows where `row_mask` is False (inactive decode slots) get no gate."""
    gates, idx, aux = _route(router_w, x2d, cfg)
    local = idx - cfg.first_held                            # [T, k]
    onehot = (local[..., None] == jnp.arange(cfg.n_held)).astype(
        jnp.float32)                                        # [T, k, n_held]
    g = jnp.einsum("tk,tke->te", gates, onehot)
    if row_mask is not None:
        g = jnp.where(row_mask[:, None], g, 0.0)
    return g, aux


def held_expert_ffn(x2d: jnp.ndarray, gates: jnp.ndarray, w_gate, w_up,
                    w_down) -> jnp.ndarray:
    """XLA path of the held-expert layer: every held expert over every
    token, each scaled by its gate (zero when not routed). Exact, with no
    capacity; computes n_held experts per token."""
    a = jnp.einsum("td,edf->etf", x2d, w_gate,
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("td,edf->etf", x2d, w_up,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * b * gates.T[..., None]).astype(w_down.dtype)
    y = jnp.einsum("etf,efd->td", h, w_down,
                   preferred_element_type=jnp.float32)
    return y.astype(x2d.dtype)


def moe_held_forward(params: dict, cfg: MoEConfig, x: jnp.ndarray,
                     row_mask: Optional[jnp.ndarray] = None,
                     kernel: bool = False, layer=None):
    """The held experts' share of the MoE layer. x [B, T, d]; `row_mask`
    [B] marks live rows (decode); `kernel` runs the Pallas grouped matmul
    (one-token decode). With `layer` (may be traced) the expert leaves of
    `params` are every layer's, stacked, and `layer` picks this one (a
    layer scan passes them whole, so the kernel reads them in place).
    Returns (y, aux, stats) with stats int32 [hit, assign]: held experts
    with at least one token, and assignments to held experts, over the
    live rows."""
    from repro.kernels import ops as kernel_ops
    assert cfg.n_shared_experts == 0, "held experts with a shared expert"
    B, T, d = x.shape
    x2d = x.reshape(B * T, d)
    rm = None if row_mask is None else jnp.repeat(row_mask, T)
    gates, aux = held_gates(params["router"], x2d, cfg, rm)
    routed = gates > 0
    stats = jnp.stack([jnp.any(routed, axis=0).sum(),
                       routed.sum()]).astype(jnp.int32)
    w = [params[k] for k in ("w_gate", "w_up", "w_down")]
    if kernel:
        y = kernel_ops.moe_gmm_decode(x2d, gates, *w, layer)
    else:
        if layer is not None:
            w = [a[layer] for a in w]
        y = held_expert_ffn(x2d, gates, *w)
    return y.reshape(B, T, d), aux, stats


def _expert_ffn(xs: jnp.ndarray, w_gate, w_up, w_down,
                group_sizes: jnp.ndarray) -> jnp.ndarray:
    """Grouped SwiGLU over sorted assignments. xs [A, d] sorted by expert;
    weights [E, d, f]; group_sizes [E]."""
    g = jax.lax.ragged_dot(xs, w_gate, group_sizes)
    u = jax.lax.ragged_dot(xs, w_up, group_sizes)
    h = swiglu(g, u)
    return jax.lax.ragged_dot(h, w_down, group_sizes)


def _expert_ffn_capacity(xflat: jnp.ndarray, eflat: jnp.ndarray,
                         w_gate, w_up, w_down, n_experts: int,
                         capacity_factor: float = 2.0) -> jnp.ndarray:
    """Per-expert-capacity batched SwiGLU (GShard-style block-diagonal).

    xflat [A, d] assignment rows; eflat [A] LOCAL expert id, with the
    sentinel id `n_experts` marking padding rows. Rows are packed into an
    [E+1, cap, d] buffer (sentinel bucket last, zero weights) and computed
    with batched einsums — FLOPs are E*cap*d*f, i.e. within capacity_factor
    of the useful work, unlike `lax.ragged_dot` whose XLA fallback computes
    every group densely (E x waste; verified in-container). On TPU this is
    also the MXU-friendly form. Per-expert overflow drops rows (standard
    GShard semantics). Returns [A, d] with dropped/padding rows zeroed.
    """
    A, d = xflat.shape
    cap = int(np.ceil(A / n_experts * capacity_factor))
    cap = max(8, int(np.ceil(cap / 8)) * 8)
    onehot = jax.nn.one_hot(eflat, n_experts + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    slot = (pos * onehot).sum(-1)
    slot = jnp.where(eflat < n_experts, slot, cap)        # drop sentinel
    buf = jnp.zeros((n_experts + 1, cap, d), xflat.dtype)
    buf = buf.at[jnp.minimum(eflat, n_experts), slot].set(xflat, mode="drop")
    wz = lambda w: jnp.concatenate(
        [w, jnp.zeros((1,) + w.shape[1:], w.dtype)], axis=0)
    h = swiglu(jnp.einsum("ecd,edf->ecf", buf, wz(w_gate)),
               jnp.einsum("ecd,edf->ecf", buf, wz(w_up)))
    out = jnp.einsum("ecf,efd->ecd", h, wz(w_down))
    res = out[jnp.minimum(eflat, n_experts), jnp.minimum(slot, cap - 1)]
    keep = ((slot < cap) & (eflat < n_experts))[:, None]
    return res * keep.astype(res.dtype)


def _moe_local_math(x2d, router_w, w_gate, w_up, w_down, cfg: MoEConfig):
    """Single-device oracle: full sort + ragged_dot over all experts."""
    T, d = x2d.shape
    gates, idx, aux = _route(router_w, x2d, cfg)
    A = T * cfg.top_k
    flat_e = idx.reshape(A)
    flat_g = gates.reshape(A)
    order = jnp.argsort(flat_e)
    tok = order // cfg.top_k
    xs = x2d[tok]
    group_sizes = jnp.bincount(flat_e, length=cfg.n_experts).astype(jnp.int32)
    out = _expert_ffn(xs, w_gate, w_up, w_down, group_sizes)
    y = jnp.zeros((T, d), out.dtype).at[tok].add(
        out * flat_g[order][:, None].astype(out.dtype))
    return y.astype(x2d.dtype), aux


def _pack_by_destination(x2d, tok, dst, valid, n_dst: int, capacity: int):
    """Scatter assignment rows into per-destination capacity buffers.

    Returns (buffer [n_dst, capacity, d], slot [A] position used (>=capacity
    means dropped)).
    """
    onehot = jax.nn.one_hot(dst, n_dst, dtype=jnp.int32) * valid[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot          # rank within dest
    slot = (pos * onehot).sum(-1)                      # [A]
    slot = jnp.where(valid.astype(bool), slot, capacity)   # invalid -> dropped
    buf = jnp.zeros((n_dst, capacity, x2d.shape[-1]), x2d.dtype)
    buf = buf.at[dst, slot].set(x2d[tok], mode="drop")
    return buf, slot


def _moe_scatter_shard(x_loc, router_w, w_gate_loc, w_up_loc, w_down_loc,
                       cfg: MoEConfig, model_axis: str, mp: int):
    """Per-device body of the training/prefill MoE (inside shard_map).

    x_loc: [T_loc, d] tokens local to this device (sharded over data AND
    model). Expert weights: local shard [E_loc, d, f].
    """
    T_loc, d = x_loc.shape
    E = cfg.n_experts
    E_loc = E // mp
    gates, idx, aux = _route(router_w, x_loc, cfg)
    A = T_loc * cfg.top_k
    flat_e = idx.reshape(A)
    flat_g = gates.reshape(A)
    tok = jnp.arange(A) // cfg.top_k
    dst = flat_e // E_loc                               # owner shard
    cap = int(np.ceil(A / mp * cfg.capacity_factor))
    cap = max(8, int(np.ceil(cap / 8)) * 8)
    valid = jnp.ones((A,), jnp.int32)
    xsend, slot = _pack_by_destination(x_loc, tok, dst, valid, mp, cap)
    esend = jnp.full((mp, cap), E_loc, jnp.int32)      # sentinel = padding
    esend = esend.at[dst, slot].set(flat_e % E_loc, mode="drop")
    # exchange: after all_to_all, row m holds what shard m sent here
    xrecv = jax.lax.all_to_all(xsend, model_axis, 0, 0, tiled=True)
    erecv = jax.lax.all_to_all(esend, model_axis, 0, 0, tiled=True)
    # per-expert-capacity grouped compute over local experts
    xflat = xrecv.reshape(mp * cap, d)
    eflat = erecv.reshape(mp * cap)
    out = _expert_ffn_capacity(xflat, eflat, w_gate_loc, w_up_loc,
                               w_down_loc, E_loc,
                               capacity_factor=2.0 * cfg.capacity_factor)
    yrecv = out.reshape(mp, cap, d).astype(x_loc.dtype)
    ysend = jax.lax.all_to_all(yrecv, model_axis, 0, 0, tiled=True)
    # combine: gather each assignment's result from (dst, slot)
    res = ysend[dst, jnp.minimum(slot, cap - 1)]
    res = res * (slot < cap)[:, None].astype(res.dtype)
    y = jnp.zeros((T_loc, d), res.dtype).at[tok].add(
        res * flat_g[:, None].astype(res.dtype))
    dropped = (slot >= cap).astype(jnp.float32).mean()
    return y.astype(x_loc.dtype), aux, dropped


def _moe_decode_shard(x_loc, router_w, w_gate_loc, w_up_loc, w_down_loc,
                      cfg: MoEConfig, model_axis: str, mp: int):
    """Decode-path body: x_loc [T, d] REPLICATED over model axis; each shard
    computes contributions of its local experts, psum combines."""
    T, d = x_loc.shape
    E = cfg.n_experts
    E_loc = E // mp
    gates, idx, aux = _route(router_w, x_loc, cfg)
    A = T * cfg.top_k
    flat_e = idx.reshape(A)
    flat_g = gates.reshape(A)
    tok = jnp.arange(A) // cfg.top_k
    shard = jax.lax.axis_index(model_axis)
    base = shard * E_loc
    local = (flat_e >= base) & (flat_e < base + E_loc)
    e_loc = jnp.clip(flat_e - base, 0, E_loc - 1)
    # pack local assignments into a small capacity buffer
    cap = int(np.ceil(A / mp * 2.0))
    cap = max(8, int(np.ceil(cap / 8)) * 8)
    rank = jnp.cumsum(local.astype(jnp.int32)) - local.astype(jnp.int32)
    slot = jnp.where(local, rank, cap)
    xbuf = jnp.zeros((cap, d), x_loc.dtype).at[slot].set(x_loc[tok], mode="drop")
    ebuf = jnp.full((cap,), E_loc, jnp.int32).at[slot].set(e_loc, mode="drop")
    out = _expert_ffn_capacity(xbuf, ebuf, w_gate_loc, w_up_loc, w_down_loc,
                               E_loc, capacity_factor=2.0)
    res = out[jnp.minimum(slot, cap - 1)]
    res = res * ((slot < cap) & local)[:, None].astype(res.dtype)
    y = jnp.zeros((T, d), res.dtype).at[tok].add(
        res * flat_g[:, None].astype(res.dtype))
    y = jax.lax.psum(y, model_axis)
    aux = jax.lax.pmean(aux, model_axis)
    return y.astype(x_loc.dtype), aux


def _axes_of(spec_entry) -> tuple:
    """PartitionSpec entry -> tuple of mesh axis names."""
    if spec_entry is None:
        return ()
    if isinstance(spec_entry, str):
        return (spec_entry,)
    return tuple(spec_entry)


def _gather_dim(w, axes, dim):
    """Explicit FSDP-style all-gather of weight dim `dim` over mesh axes."""
    for ax in axes:
        w = jax.lax.all_gather(w, ax, axis=dim, tiled=True)
    return w


def moe_forward(params: dict, cfg: MoEConfig, x: jnp.ndarray,
                ctx: ParallelContext, decode: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE block. x [B, T, d] -> (y, aux_loss). Dispatches to the local,
    scatter (train/prefill) or decode path based on ctx/mesh.

    shard_map in_specs are DERIVED from the sharding rules so the step's
    parameter shardings and the shard_map body always agree (no silent
    GSPMD reshard). Two weight layouts are supported:
      * expert_embed sharded (default, ZeRO-3): the body all-gathers the
        weight's d-dim per layer before use — right for training where
        tokens >> weights.
      * expert_ffn sharded (decode hillclimb): weights stay put; the body
        all-gathers the TOKENS over the ffn-sharding axis, computes
        partial results against its (expert, f-slice) shard, psums, and
        slices its token rows back — right for decode where
        weights >> tokens (2 TB vs 1.8 MB for kimi-k2).
    """
    B, T, d = x.shape
    mp = ctx.model_parallel_size
    shared_y = 0.0
    if cfg.n_shared_experts > 0:
        sf = cfg.shared_d_ff or cfg.d_ff * cfg.n_shared_experts
        shared_y = mlp_forward(params["shared"], MLPConfig(d, sf, "swiglu"),
                               x, ctx)

    if ctx.mesh is None or mp == 1 or cfg.n_experts % mp != 0:
        x2d = x.reshape(B * T, d)
        y, aux = _moe_local_math(x2d, params["router"], params["w_gate"],
                                 params["w_up"], params["w_down"], cfg)
        return shared_y + y.reshape(B, T, d), aux

    mesh = ctx.mesh
    ma = ctx.model_axis
    batch_axes = tuple(a for a in (ctx.pod_axis, ctx.data_axis)
                       if a is not None and B % _axis_size(mesh, a) == 0)
    from jax.sharding import PartitionSpec as P
    from repro.sharding import logical_to_spec

    E, f = cfg.n_experts, cfg.d_ff
    rspec = logical_to_spec(("embed", "experts"), (d, E), mesh, ctx.rules)
    gspec = logical_to_spec(("experts", "expert_embed", "expert_ffn"),
                            (E, d, f), mesh, ctx.rules)
    dspec = logical_to_spec(("experts", "expert_ffn", "expert_embed"),
                            (E, f, d), mesh, ctx.rules)
    wspec = {"router": rspec, "w_gate": gspec, "w_up": gspec,
             "w_down": dspec}
    r_d_axes = _axes_of(rspec[0])
    r_e_axes = _axes_of(rspec[1])        # router must see ALL experts
    d_axes = _axes_of(gspec[1])          # expert_embed mesh axes
    f_axes = _axes_of(gspec[2])          # expert_ffn mesh axes
    assert len(f_axes) <= 1, "one ffn-sharding axis supported"

    def prep_weights(rw, wg, wu, wd):
        rw = _gather_dim(_gather_dim(rw, r_d_axes, 0), r_e_axes, 1)
        wg = _gather_dim(wg, d_axes, 1)
        wu = _gather_dim(wu, d_axes, 1)
        wd = _gather_dim(wd, d_axes, 2)
        return rw, wg, wu, wd

    if not decode and T % mp == 0:
        # scatter path: tokens over (batch axes, model); weights gathered
        # along any FSDP dims (tokens >> weights in training)
        xspec = P(batch_axes if batch_axes else None, ma, None)

        def body(xl, rw, wg, wu, wd):
            Bl, Tl, _ = xl.shape
            rw, wg, wu, wd = prep_weights(rw, wg, wu, wd)
            # scatter path computes against full-f experts
            wg = _gather_dim(wg, f_axes, 2)
            wu = _gather_dim(wu, f_axes, 2)
            wd = _gather_dim(wd, f_axes, 1)
            y, aux, dropped = _moe_scatter_shard(
                xl.reshape(Bl * Tl, d), rw, wg, wu, wd, cfg, ma, mp)
            # aux/dropped are per-device scalars; mean over ALL axes so the
            # outputs are replicated (shard_map out_spec P())
            allaxes = tuple(mesh.axis_names)
            aux = jax.lax.pmean(aux, allaxes)
            dropped = jax.lax.pmean(dropped, allaxes)
            return y.reshape(Bl, Tl, d), aux, dropped

        y, aux, _dropped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(xspec, wspec["router"], wspec["w_gate"],
                      wspec["w_up"], wspec["w_down"]),
            out_specs=(xspec, P(), P()),
            check_vma=False,
        )(x, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])
        return shared_y + y, aux

    # decode path: tokens replicated over model, sharded over batch axes
    xspec = P(batch_axes if batch_axes else None, None, None)
    tok_gather_axes = tuple(a for a in f_axes if a in batch_axes)

    def body_dec(xl, rw, wg, wu, wd):
        Bl, Tl, _ = xl.shape
        rw, wg, wu, wd = prep_weights(rw, wg, wu, wd)
        x2 = xl.reshape(Bl * Tl, d)
        # "gather tokens, not weights": bring every device's tokens in,
        # compute against the local (E/mp, d, f/|f_axes|) weight shard,
        # psum the partial results, slice our token rows back out.
        for ax in tok_gather_axes:
            x2 = jax.lax.all_gather(x2, ax, axis=0, tiled=True)
        y, aux = _moe_decode_shard(x2, rw, wg, wu, wd, cfg, ma, mp)
        for ax in f_axes:
            # partial sums over the f-slice; tokens replicated over any
            # f-axis NOT in batch_axes, so psum alone is correct there
            y = jax.lax.psum(y, ax)
            if ax in tok_gather_axes:
                idx = jax.lax.axis_index(ax) * Bl * Tl
                y = jax.lax.dynamic_slice_in_dim(y, idx, Bl * Tl, 0)
        aux = jax.lax.pmean(aux, tuple(a for a in mesh.axis_names if a != ma))
        return y.reshape(Bl, Tl, d), aux

    y, aux = jax.shard_map(
        body_dec, mesh=mesh,
        in_specs=(xspec, wspec["router"], wspec["w_gate"], wspec["w_up"],
                  wspec["w_down"]),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return shared_y + y, aux


def _axis_size(mesh, name):
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)
