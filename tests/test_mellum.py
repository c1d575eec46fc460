"""Mellum2's mechanisms at small sizes on the CPU, against closed forms
and the plain reference (`kernels/ref.py`): YaRN rope, windowed layers in
chunked paged prefill, and the held-expert layer (its shares, its decode
kernel, no dropped token)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import moe_gmm
from repro.kernels import ops as kops
from repro.kernels.ref import lm_forward_ref
from repro.models import ffn
from repro.models import transformer as tfm
from repro.models.common import (apply_rope_freqs, rope_inv_freq,
                                 yarn_frequencies)
from repro.sharding import ParallelContext

CTX = ParallelContext()
FULL = get_config("mellum2-12b-a2.5b").layer_kinds[-1].rope


@pytest.mark.parametrize("hd", [128, 32])
def test_yarn_frequencies_closed_form(hd):
    """The full layers' YaRN rope against the closed form: dimension pair
    i turns at b^(-2i/hd) where it turns more than beta_fast = 32 times
    over the original 8192 positions, at b^(-2i/hd) / 16 where it turns
    fewer than beta_slow = 1 times, linearly blended between (the bounds
    rounded outward), and cos and sin carry the attention factor."""
    b = 500000.0
    # dimension that turns r times over 8192 positions
    dim = lambda r: hd * math.log(8192 / (2 * math.pi * r)) / (   # noqa
        2 * math.log(b))
    lo, hi = math.floor(dim(32)), math.ceil(dim(1))
    if hd == 128:
        assert (lo, hi) == (18, 35)
    i = np.arange(hd // 2)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    want = b ** (-2 * i / hd) * (1 - ramp) + b ** (-2 * i / hd) / 16 * ramp
    got = yarn_frequencies(hd, FULL)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # a unit vector in each pair's first half turns to (cos, sin) x factor
    pos = jnp.asarray([0, 1, 7, 4000])
    x = jnp.zeros((4, 1, hd)).at[..., : hd // 2].set(1.0)
    freqs, factor = rope_inv_freq(hd, 0.0, FULL)
    assert factor == FULL.attention_factor
    out = np.asarray(apply_rope_freqs(x, pos, jnp.asarray(freqs),
                                      factor))[:, 0]
    # the angle as the program forms it, in float32 (at position 4000 the
    # rounding of pos * f alone is ~2e-4 rad)
    ang = (np.asarray(pos, np.float32)[:, None]
           * want.astype(np.float32)[None, :]).astype(np.float64)
    np.testing.assert_allclose(out[:, : hd // 2],
                               np.cos(ang) * FULL.attention_factor,
                               atol=2e-5)
    np.testing.assert_allclose(out[:, hd // 2:],
                               np.sin(ang) * FULL.attention_factor,
                               atol=2e-5)


@pytest.fixture(scope="module")
def mellum():
    cfg = reduced(get_config("mellum2-12b-a2.5b"))
    params = tfm.init_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def test_reduced_mellum_keeps_its_mechanisms(mellum):
    cfg, _ = mellum
    assert [k.window for k in cfg.layer_kinds] == [16, 16, 16, None]
    assert cfg.layer_kinds[-1].rope.kind == "yarn"
    assert (cfg.moe.n_held, cfg.moe.n_experts, cfg.moe.n_dense_layers) == (
        2, 4, 0)


@pytest.mark.parametrize("block,chunk", [(4, 8), (8, 12)],
                         ids=["chunk_multiple", "chunk_ragged"])
def test_chunked_paged_prefill_window_matches_reference(mellum, block,
                                                        chunk):
    """A 45-token prompt (past the 16-token window) prefilled in chunks
    through the page table, then decoded one token at a time through it
    (XLA path and the kernels), agrees with the reference's full forward
    pass at every chunk's last position and every decoded position."""
    cfg, params = mellum
    T, n_dec = 45, 4
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (T + n_dec,),
                                         0, cfg.vocab_size), np.int32)
    ref = np.asarray(lm_forward_ref(params, cfg, jnp.asarray(toks)))
    M = -(-(T + n_dec) // block)
    tables = jnp.arange(1, M + 1, dtype=jnp.int32)[None]
    scale = np.abs(ref).max()
    for kernel in (False, True):
        # paged leaves [layers, M + 1 blocks (0 = trash), block, ...]
        cache = tfm.init_cache(cfg, M + 1, block, dtype=jnp.float32)
        for off in range(0, T, chunk):
            n = min(chunk, T - off)
            part = np.zeros((1, chunk), np.int32)
            part[0, :n] = toks[off:off + n]
            lg, cache = tfm.prefill(params, cfg, jnp.asarray(part), cache,
                                    CTX, cache_offset=off, pages=tables,
                                    last_index=n - 1)
            np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                       ref[off + n - 1], atol=2e-5 * scale)
        for t in range(T, T + n_dec):
            lg, cache = tfm.decode_step(params, cfg, jnp.asarray([toks[t]]),
                                        jnp.asarray([t]), cache, CTX,
                                        pages=tables, paged_kernel=kernel)
            np.testing.assert_allclose(np.asarray(lg[0]), ref[t],
                                       atol=2e-5 * scale)


def _moe(n_experts=8, top_k=3, n_held=None, first=0, d=32, f=48):
    return ffn.MoEConfig(d_model=d, d_ff=f, n_experts=n_experts,
                         top_k=top_k, n_held=n_held, first_held=first)


def _moe_params(cfg, key, scale_router=1.0):
    ks = jax.random.split(key, 4)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {"router": jax.random.normal(ks[0], (d, E)) * scale_router
            / np.sqrt(d),
            "w_gate": jax.random.normal(ks[1], (E, d, f)) / np.sqrt(d),
            "w_up": jax.random.normal(ks[2], (E, d, f)) / np.sqrt(d),
            "w_down": jax.random.normal(ks[3], (E, f, d)) / np.sqrt(f)}


def _share(p, first, n):
    return dict(p, **{k: p[k][first:first + n]
                      for k in ("w_gate", "w_up", "w_down")})


def test_expert_shares_sum_to_the_uncut_layer():
    """Every device's share (2 of 8 experts each, routed over all 8) of
    the layer, added up, is the uncut layer (the single-device oracle)."""
    cfg = _moe()
    p = _moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = ffn._moe_local_math(x.reshape(-1, cfg.d_model),
                                       p["router"], p["w_gate"], p["w_up"],
                                       p["w_down"], cfg)
        total = 0.0
        assign = 0
        for first in range(0, 8, 2):
            share = _moe(n_held=2, first=first)
            y, _, st = ffn.moe_held_forward(_share(p, first, 2), share, x)
            total = total + y
            assign += int(st[1])
    np.testing.assert_allclose(np.asarray(total).reshape(-1, cfg.d_model),
                               np.asarray(whole), atol=1e-5)
    assert assign == 15 * cfg.top_k          # every assignment, once


def _ragged_held(x, gates, p):
    """The sorted `lax.ragged_dot` path over the held experts: each
    (token, held expert) assignment with a gate, grouped by expert;
    experts with no token are empty groups."""
    T, E = gates.shape
    tok, exp = np.nonzero(np.asarray(gates) > 0)
    order = np.argsort(exp, kind="stable")
    tok, exp = tok[order], exp[order]
    sizes = jnp.asarray(np.bincount(exp, minlength=E), jnp.int32)
    out = ffn._expert_ffn(x[tok], p["w_gate"], p["w_up"], p["w_down"],
                          sizes)
    g = np.asarray(gates)[tok, exp][:, None]
    return jnp.zeros_like(x).at[tok].add(out * g)


@pytest.mark.parametrize("case", ["mixed", "one_row", "no_row", "tiled",
                                  "stacked"])
def test_moe_kernel_matches_ragged_dot(case, monkeypatch):
    """The decode kernel (interpret mode) against the ragged_dot path:
    held experts that took no token are skipped (and read: never); rows
    outside `row_mask` route nowhere; with a small VMEM budget the expert
    width streams in tiles; given every layer's experts stacked, it reads
    the one layer a traced index picks."""
    f = 384 if case == "tiled" else 48
    if case == "tiled":
        monkeypatch.setattr(moe_gmm, "_VMEM_BUDGET", 2 * 3 * 32 * 128 * 4)
        assert moe_gmm.ffn_tile(32, f, 4) == 128
    cfg = _moe(n_held=4, first=2, top_k=2, f=f)
    p = _moe_params(_moe(f=f), jax.random.PRNGKey(2))
    p = _share(p, 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (6, cfg.d_model))
    mask = {"mixed": [True] * 6, "tiled": [True] * 6, "stacked": [True] * 6,
            "one_row": [False, False, True, False, False, False],
            "no_row": [False] * 6}[case]
    gates, _ = ffn.held_gates(p["router"], x, cfg, jnp.asarray(mask))
    hit = int((np.asarray(gates) > 0).any(0).sum())
    if case == "one_row":
        assert 0 < hit < 4
    w = [p[k] for k in ("w_gate", "w_up", "w_down")]
    layer = None
    if case == "stacked":       # layer 2 of 3; the others hold noise
        w = [jnp.stack([a * 3.0, -a, a]) for a in w]
        layer = jnp.int32(2)
    with jax.default_matmul_precision("highest"):
        got = kops.moe_gmm_decode(x, gates, *w, layer)
        want = _ragged_held(x, gates, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    if case == "no_row":
        np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_no_token_dropped_under_skewed_routing():
    """Every token routed to the same two held experts (a router that
    prefers them for every token): the serving path, on the kernel and on
    the XLA path, computes every assignment; no capacity drops any."""
    cfg = _moe(n_held=4, first=0, top_k=2)
    p = _share(_moe_params(_moe(), jax.random.PRNGKey(4)), 0, 4)
    p["router"] = p["router"].at[:, :2].add(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5),
                                  (1, 64, cfg.d_model)))
    gates, _ = ffn.held_gates(p["router"], x[0], cfg)
    assert (np.asarray(gates)[:, 2:] == 0).all()
    assert (np.asarray(gates)[:, :2] > 0).all()
    with jax.default_matmul_precision("highest"):
        want = _ragged_held(x[0], gates, p)
        for kernel in (False, True):
            y, _, st = ffn.moe_held_forward(p, cfg, x, kernel=kernel)
            np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                                       atol=1e-5)
            assert [int(s) for s in st] == [2, 64 * 2]
