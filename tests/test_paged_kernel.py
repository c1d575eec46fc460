"""Paged flash-decode kernel tests: interpret-mode parity of the Pallas
GQA/MLA paged decode kernels (and the in-kernel single-token paged write)
against the XLA dense-gather path, active-prefix gather equivalence, and
engine-level greedy parity of the kernel path and of batched paged
prefill vs the serial chunk loop on a ragged Poisson stream."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.data.synthetic import make_lm_stream
from repro.kernels import ops as kops
from repro.models import transformer as tfm
from repro.models import attention as attn
from repro.models.attention import _paged_write, gather_blocks
from repro.serving import (ContinuousCascadeEngine, ModelRunner,
                           make_requests, poisson_arrivals)
from repro.serving.request import DONE
from repro.sharding import ParallelContext


@pytest.fixture(scope="module")
def runners():
    key = jax.random.PRNGKey(0)
    s_cfg = reduced(get_config("internlm2-1.8b"))
    l_cfg = s_cfg.replace(name="large", n_layers=3, d_ff=768)
    small = ModelRunner(s_cfg, tfm.init_params(s_cfg, key))
    large = ModelRunner(l_cfg, tfm.init_params(l_cfg,
                                               jax.random.fold_in(key, 1)))
    return small, large


def ragged_prompts(key, lens, vocab):
    base = make_lm_stream(key, len(lens), max(lens), vocab)
    return [base[i, :n].astype(np.int32) for i, n in enumerate(lens)]


# page table with disjoint nonzero blocks per row + one all-trash row;
# positions ragged, one mid-block
TABLES = np.asarray([[1, 2, 3, 0],
                     [4, 5, 0, 0],
                     [6, 0, 0, 0],
                     [0, 0, 0, 0]], np.int32)
POS = np.asarray([9, 6, 2, 3], np.int32)       # rows 0-2 mapped, row 3 trash


# ---------------------------------------------------------------------------
# Kernel-level parity vs the dense-gather reference
# ---------------------------------------------------------------------------

# GQA kernel cases. Tiny widths (block 4, 2 KV heads of 16) give 16 pages
# per compute block; the served widths (block 16, 8 KV heads of 128) give
# 8 in bf16 and 4 in fp32. Every row maps all M columns unless `unmapped`
# (row, columns) says otherwise; pages no row may read hold NaN.
SERVED = (16, 8, 128, 16)                       # bs, KV, hd, H
SERVED_PAGES = 1 + 4 * 13                       # one pool for M 13 and 1
GQA_CASES = {
    # the original ragged table: M (4) below the block's 16 pages
    "ragged": dict(dims=(4, 2, 16, 4), tables=TABLES, pos=POS,
                   dtype=jnp.float32, atol=1e-5),
    # M 13 = 8 + 5: each row's last block is ragged, ends mid-block
    "m_not_multiple": dict(dims=SERVED, M=13, pos=[200, 150, 40, 7],
                           dtype=jnp.bfloat16, atol=2e-5),
    # ends on the last token of block 0, the first of block 1, the last
    # of the table; one mid-block
    "block_boundary": dict(dims=SERVED, M=13, pos=[127, 128, 207, 143],
                           dtype=jnp.bfloat16, atol=2e-5),
    # holes in both blocks; a first page unmapped; a whole first block
    # unmapped; trailing live columns unmapped
    "unmapped_inside": dict(dims=SERVED, M=13, pos=[200, 100, 150, 180],
                            unmapped=[(0, [3, 8]), (1, [0]),
                                      (2, list(range(8))),
                                      (3, list(range(5, 13)))],
                            dtype=jnp.bfloat16, atol=2e-5),
    # the first and last rows all trash: the prefetch chain skips them
    "all_trash": dict(dims=SERVED, M=13, pos=[50, 120, 9, 60],
                      unmapped=[(0, list(range(13))),
                                (3, list(range(13)))],
                      dtype=jnp.bfloat16, atol=2e-5),
    "m1": dict(dims=SERVED, M=1, pos=[3, 15, 0, 9],
               unmapped=[(2, [0])], dtype=jnp.bfloat16, atol=2e-5),
    # fp32 pages: 4 per block, so M 13 leaves a 1-page last block
    "fp32": dict(dims=SERVED, M=13, pos=[200, 63, 64, 5],
                 dtype=jnp.float32, atol=1e-5),
    # bf16 q as well: the output is rounded to bf16 (2^-9 relative)
    "bf16_q": dict(dims=SERVED, M=13, pos=[200, 150, 40, 7],
                   dtype=jnp.bfloat16, q_dtype=jnp.bfloat16, atol=1e-2),
}


# windowed cases: a row attends to (pos - window, pos]; its walk starts
# at the page holding pos - window + 1, so every page before it holds NaN
WINDOW_CASES = {
    # window 6 over 4-token pages (not a multiple): rows below, at and
    # past the window, and the all-trash row
    "tiny_not_multiple": dict(dims=(4, 2, 16, 4), tables=TABLES,
                              pos=POS, window=6, dtype=jnp.float32,
                              atol=1e-5),
    # window 20 over 16-token pages: rows below (10), at (19, 20) and
    # far past it (200, the walk starting mid-table)
    "served_not_multiple": dict(dims=SERVED, M=13, pos=[10, 19, 20, 200],
                                window=20, dtype=jnp.bfloat16, atol=2e-5),
    # window 32 (a multiple) ending on page boundaries, one row in it
    "served_multiple": dict(dims=SERVED, M=13, pos=[31, 32, 47, 150],
                            window=32, dtype=jnp.bfloat16, atol=2e-5),
    # all-trash first and last rows beside windowed ones
    "all_trash": dict(dims=SERVED, M=13, pos=[150, 100, 9, 60], window=37,
                      unmapped=[(0, list(range(13))), (3, list(range(13)))],
                      dtype=jnp.bfloat16, atol=2e-5),
}


def _gqa_case(case, seed=1):
    """(q, k_pages, v_pages, tables, pos) of a case. The served cases
    share one pool, so each dtype and table width compiles once."""
    bs, KV, hd, H = case["dims"]
    pos = np.asarray(case["pos"], np.int32)
    if "tables" in case:
        tables = np.asarray(case["tables"], np.int32)
    else:
        mapped = np.ones((len(pos), case["M"]), bool)
        for row, cols in case.get("unmapped", ()):
            mapped[row, cols] = False
        tables = np.zeros(mapped.shape, np.int32)
        tables[mapped] = np.arange(1, mapped.sum() + 1)
    B, M = tables.shape
    N = int(tables.max()) + 1 if "tables" in case else SERVED_PAGES
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, 1, H, hd), np.float32)
    kp, vp = rng.standard_normal((2, N, bs, KV, hd), np.float32)
    first = np.maximum(pos - case.get("window", 1 << 30) + 1, 0)
    live = ((tables > 0) & (np.arange(M)[None, :] <= pos[:, None] // bs)
            & (np.arange(M)[None, :] >= first[:, None] // bs))
    dead = np.setdiff1d(np.arange(N), tables[live])
    kp[dead] = np.nan
    vp[dead] = np.nan
    return (jnp.asarray(q, case.get("q_dtype", jnp.float32)),
            jnp.asarray(kp, case["dtype"]), jnp.asarray(vp, case["dtype"]),
            tables, pos)


def _gqa_reference(q, kp, vp, tables, pos, window=None):
    """float64 masked softmax over each row's mapped `idx <= pos` tokens
    (and `idx > pos - window` with a window); rows without one give
    zeros."""
    q, kp, vp = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (q, kp, vp))
    B, _, H, hd = q.shape
    bs, KV = kp.shape[1], kp.shape[2]
    kk = kp[tables].reshape(B, -1, KV, hd)
    vv = vp[tables].reshape(B, -1, KV, hd)
    idx = np.arange(kk.shape[1])[None, :]
    ok = (idx <= pos[:, None]) & np.repeat(tables > 0, bs, axis=1)
    if window is not None:
        ok &= idx > pos[:, None] - window
    kk = np.where(ok[..., None, None], kk, 0.0)
    vv = np.where(ok[..., None, None], vv, 0.0)
    qg = q[:, 0].reshape(B, KV, H // KV, hd)
    s = np.einsum("bkgh,bskh->bkgs", qg, kk) / np.sqrt(hd)
    s = np.where(ok[:, None, None, :], s, -np.inf)
    m = np.max(s, axis=-1, keepdims=True)
    p = np.where(ok[:, None, None, :], np.exp(s - np.where(
        np.isfinite(m), m, 0.0)), 0.0)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-300)
    return np.einsum("bkgs,bskh->bkgh", p, vv).reshape(B, 1, H, hd)


@pytest.mark.parametrize("name", list(GQA_CASES))
def test_gqa_kernel_parity_ragged(name):
    """The many-pages-per-step kernel against a float64 dense gather:
    rows with a readable token agree within the case's `atol`; rows with
    none (all trash) are exact zeros; NaN in every page no row may read
    (trash block, holes, pages past `pos`) never reaches the output."""
    case = GQA_CASES[name]
    q, kp, vp, tables, pos = _gqa_case(case)
    out = np.asarray(kops.paged_flash_decode_gqa(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(pos)), np.float64)
    ref = _gqa_reference(q, kp, vp, tables, pos)
    bs = kp.shape[1]
    readable = ((tables > 0)
                & (np.arange(tables.shape[1])[None, :]
                   <= pos[:, None] // bs)).any(axis=1)
    np.testing.assert_allclose(out[readable], ref[readable],
                               atol=case["atol"], rtol=0)
    np.testing.assert_array_equal(out[~readable],
                                  np.zeros_like(out[~readable]))


@pytest.mark.parametrize("name", list(WINDOW_CASES))
def test_gqa_kernel_window(name):
    """A windowed layer's call against a float64 dense windowed softmax:
    rows below, at and past the window agree; pages before the window's
    first page hold NaN and are never read; all-trash rows are zeros."""
    case = WINDOW_CASES[name]
    q, kp, vp, tables, pos = _gqa_case(case)
    out = np.asarray(kops.paged_flash_decode_gqa(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(pos),
        jnp.int32(case["window"])), np.float64)
    ref = _gqa_reference(q, kp, vp, tables, pos, case["window"])
    readable = (tables > 0).any(axis=1)
    np.testing.assert_allclose(out[readable], ref[readable],
                               atol=case["atol"], rtol=0)
    np.testing.assert_array_equal(out[~readable],
                                  np.zeros_like(out[~readable]))


@pytest.mark.parametrize("window", [6, 8, 3])
def test_gqa_decode_window_layer_paths(window):
    """One windowed attention layer: the paged kernel, the paged XLA path
    and dense (unpaged) windowed attention over the same rows agree on
    every mapped row, for windows off and on the 4-token page size."""
    ac, params, cache, x = _gqa_layer(jax.random.PRNGKey(6))
    ac = dataclasses.replace(ac, sliding_window=window)
    ctx = ParallelContext()
    tables, pos = jnp.asarray(TABLES), jnp.asarray(POS)
    outs = [attn.gqa_decode(params, ac, x, pos, cache, ctx, pages=tables,
                            paged_kernel=k)[0] for k in (False, True)]
    # dense: each row's pages gathered into a linear cache, no table
    dense = {k: gather_blocks(v, tables) for k, v in cache.items()}
    y_d, _ = attn.gqa_decode(params, ac, x, pos, dense, ctx)
    for y in outs:
        np.testing.assert_allclose(np.asarray(y[:3]), np.asarray(y_d[:3]),
                                   atol=1e-5)


def test_mla_kernel_parity_ragged():
    key = jax.random.PRNGKey(2)
    B, H, r, dr, bs, N = 4, 4, 8, 6, 4, 8
    ks = jax.random.split(key, 5)
    q_abs = jax.random.normal(ks[0], (B, 1, H, r), jnp.float32)
    q_rope = jax.random.normal(ks[1], (B, 1, H, dr), jnp.float32)
    ckv = jax.random.normal(ks[2], (N, bs, r), jnp.float32)
    kr = jax.random.normal(ks[3], (N, bs, dr), jnp.float32)
    w = jax.random.normal(ks[4], (r,), jnp.float32) * 0.1
    tables, pos = jnp.asarray(TABLES), jnp.asarray(POS)
    scale = 1.0 / np.sqrt(16 + dr)

    out = kops.paged_flash_decode_mla(q_abs, q_rope, ckv, kr, w, tables,
                                      pos, scale=scale)

    from repro.models.common import rms_norm
    ckv_all = gather_blocks(ckv, tables)
    kr_all = gather_blocks(kr, tables)
    ckv_n = rms_norm(ckv_all, w)
    S = ckv_all.shape[1]
    s = (jnp.einsum("bthr,bsr->bhts", q_abs, ckv_n)
         + jnp.einsum("bthk,bsk->bhts", q_rope, kr_all)) * scale
    mask = (jnp.arange(S)[None, :] <= pos[:, None])[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    ref = jnp.einsum("bhts,bsr->bthr", jax.nn.softmax(s, axis=-1), ckv_n)
    np.testing.assert_allclose(np.asarray(out[:3]), np.asarray(ref[:3]),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out[3]),
                                  np.zeros_like(np.asarray(out[3])))


def test_paged_write_kernel_matches_xla():
    key = jax.random.PRNGKey(3)
    N, bs, KV, hd, B = 8, 4, 2, 16, 4
    leaf = jax.random.normal(key, (N, bs, KV, hd), jnp.float32)
    vals = jax.random.normal(jax.random.fold_in(key, 1), (B, KV, hd))
    tables, pos = jnp.asarray(TABLES), jnp.asarray(POS)
    out = kops.paged_write_token(leaf, tables, pos, vals)
    ref = _paged_write(leaf, tables, pos[:, None], vals[:, None])
    # bit parity, including the trash-row write into block 0
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # MLA-shaped leaf (no head dims beyond one feature axis)
    leaf2 = jax.random.normal(key, (N, bs, 7), jnp.float32)
    vals2 = jax.random.normal(jax.random.fold_in(key, 2), (B, 7))
    np.testing.assert_array_equal(
        np.asarray(kops.paged_write_token(leaf2, tables, pos, vals2)),
        np.asarray(_paged_write(leaf2, tables, pos[:, None],
                                vals2[:, None])))


# ---------------------------------------------------------------------------
# Attention-level: kernel vs fallback, active-prefix gather equivalence
# ---------------------------------------------------------------------------

def _gqa_layer(key):
    cfg = reduced(get_config("internlm2-1.8b"))
    ac = tfm.attn_config(cfg)
    params = jax.tree.map(lambda a: a[0],
                          tfm.init_params(cfg, key)["blocks"]["dense"]["attn"])
    ks = jax.random.split(jax.random.fold_in(key, 7), 3)
    cache = {
        "k": jax.random.normal(ks[0], (8, 4, ac.n_kv_heads, ac.head_dim)) * .1,
        "v": jax.random.normal(ks[1], (8, 4, ac.n_kv_heads, ac.head_dim)) * .1}
    x = jax.random.normal(ks[2], (4, 1, cfg.d_model)) * 0.3
    return ac, params, cache, x


def test_gqa_decode_kernel_vs_fallback_layer():
    """One attention layer: same inputs through both paged decode
    implementations -> outputs match to epsilon on mapped rows and the
    written caches are BIT-identical (the write kernel scatters exactly
    what the XLA scatter does)."""
    ac, params, cache, x = _gqa_layer(jax.random.PRNGKey(4))
    ctx = ParallelContext()
    tables, pos = jnp.asarray(TABLES), jnp.asarray(POS)
    y_f, c_f = attn.gqa_decode(params, ac, x, pos, cache, ctx,
                               pages=tables, paged_kernel=False)
    y_k, c_k = attn.gqa_decode(params, ac, x, pos, cache, ctx,
                               pages=tables, paged_kernel=True)
    np.testing.assert_allclose(np.asarray(y_k[:3]), np.asarray(y_f[:3]),
                               atol=1e-5)
    assert np.isfinite(np.asarray(y_k)).all()
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(c_k[leaf]),
                                      np.asarray(c_f[leaf]))


def test_active_prefix_gather_equivalence():
    """Slicing the page table to the active block prefix (every mapped
    position still covered) must not change the fallback decode output
    or the cache writes at all — the tightened gather is exact."""
    ac, params, cache, x = _gqa_layer(jax.random.PRNGKey(5))
    ctx = ParallelContext()
    pos = jnp.asarray([9, 6, 2, 3], jnp.int32)      # max pos 9 -> 3 blocks
    full = jnp.asarray(TABLES)
    for kernel in (False, True):
        y_full, c_full = attn.gqa_decode(params, ac, x, pos, cache, ctx,
                                         pages=full, paged_kernel=kernel)
        y_cut, c_cut = attn.gqa_decode(params, ac, x, pos, cache, ctx,
                                       pages=full[:, :3],
                                       paged_kernel=kernel)
        np.testing.assert_array_equal(np.asarray(y_cut), np.asarray(y_full))
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(c_cut[leaf]),
                                          np.asarray(c_full[leaf]))


# the serving pool's layout: every layer of a group stacked, features flat
STACK_LAYERS = 3


def _stacked_cache(key, leaves):
    """A stacked pool `{name: [L, 8, 4, F]}` of random values."""
    ks = jax.random.split(key, len(leaves))
    return {name: jax.random.normal(k, (STACK_LAYERS, 8, 4, F)) * .1
            for k, (name, F) in zip(ks, leaves.items())}


def _mla_layer(key):
    cfg = reduced(get_config("deepseek-v2-236b"))
    cfg = cfg.replace(moe=None, family="dense", n_layers=2)
    ac = tfm.attn_config(cfg)
    params = jax.tree.map(lambda a: a[0],
                          tfm.init_params(cfg, key)["blocks"]["dense"]["attn"])
    cache = _stacked_cache(jax.random.fold_in(key, 7),
                           {"ckv": ac.kv_lora, "kr": ac.rope_dim})
    x = jax.random.normal(jax.random.fold_in(key, 8), (4, 1, cfg.d_model)) * .3
    return ac, params, cache, x


@pytest.mark.parametrize("layer", [0, STACK_LAYERS - 1])
@pytest.mark.parametrize("kind", ["gqa", "gqa_window", "mla"])
def test_stacked_pool_kernel_vs_fallback(kind, layer):
    """The serving pool's stacked leaves `[L, N, bs, F]` with the layer as
    a traced index: the kernels' write plus flash-decode matches the XLA
    fallback's scatter plus gather (outputs to epsilon on mapped rows, the
    written pools bit for bit), and neither changes a block of any other
    layer."""
    key = jax.random.PRNGKey(20)
    if kind == "mla":
        ac, params, cache, x = _mla_layer(key)
        fwd = attn.mla_decode
    else:
        ac, params, _, x = _gqa_layer(key)
        if kind == "gqa_window":
            ac = dataclasses.replace(ac, sliding_window=6)
        F = ac.n_kv_heads * ac.head_dim
        cache = _stacked_cache(jax.random.fold_in(key, 7), {"k": F, "v": F})
        fwd = attn.gqa_decode
    ctx = ParallelContext()
    tables, pos = jnp.asarray(TABLES), jnp.asarray(POS)
    run = jax.jit(lambda c, lyr, k: fwd(params, ac, x, pos, c, ctx,
                                        pages=tables, paged_kernel=k,
                                        layer_index=lyr),
                  static_argnums=2)
    y_f, c_f = run(cache, jnp.int32(layer), False)
    y_k, c_k = run(cache, jnp.int32(layer), True)
    np.testing.assert_allclose(np.asarray(y_k[:3]), np.asarray(y_f[:3]),
                               atol=1e-5)
    assert np.isfinite(np.asarray(y_k)).all()
    others = [i for i in range(STACK_LAYERS) if i != layer]
    for name, old in cache.items():
        np.testing.assert_array_equal(np.asarray(c_k[name]),
                                      np.asarray(c_f[name]))
        new = np.asarray(c_k[name])
        np.testing.assert_array_equal(new[others], np.asarray(old)[others])
        assert not np.array_equal(new[layer], np.asarray(old)[layer])


# ---------------------------------------------------------------------------
# Engine-level: kernel path + batched prefill greedy parity
# ---------------------------------------------------------------------------

def _engine(small, large, **kw):
    return ContinuousCascadeEngine(small, large, n_slots=3, tau=-1e9,
                                   early_exit=False, backend="paged",
                                   block_size=4, prefill_chunk=4, **kw)


def test_engine_kernel_parity_ragged_poisson(runners):
    """Acceptance: the continuous+paged engine on the Pallas kernel path
    (interpret mode) reproduces the dense-gather path token for token on
    a ragged Poisson stream — and both match standalone generation."""
    small, large = runners
    key = jax.random.PRNGKey(11)
    lens = [5, 9, 4, 12, 7, 6, 10, 4]
    prompts = ragged_prompts(key, lens, small.cfg.vocab_size)
    arrivals = poisson_arrivals(len(prompts), rate=300.0, seed=3)

    res_fb = _engine(small, large, paged_kernel=False).run(
        make_requests(prompts, 5, arrivals), 5)
    res_k = _engine(small, large, paged_kernel=True).run(
        make_requests(prompts, 5, arrivals), 5)

    assert res_k.stats["paged_kernel"] and not res_fb.stats["paged_kernel"]
    np.testing.assert_array_equal(res_k.tokens, res_fb.tokens)
    np.testing.assert_allclose(res_k.confidence, res_fb.confidence,
                               rtol=1e-4)
    assert all(r.state == DONE for r in res_k.requests)
    for r in res_k.requests:
        t, _ = small.generate(r.prompt[None, :], r.prompt_len, 5)
        np.testing.assert_array_equal(r.tokens, t[0])


def test_batched_prefill_parity_and_dispatch_count(runners):
    """Batched paged prefill packs same-offset chunks of simultaneous
    arrivals into one dispatch: greedy outputs equal the serial chunk
    loop bit for bit, the per-row chunk count is unchanged, and the
    dispatch count strictly drops on a batched-arrival workload."""
    small, large = runners
    key = jax.random.PRNGKey(13)
    lens = [8, 8, 12, 6, 8, 10]
    prompts = ragged_prompts(key, lens, small.cfg.vocab_size)

    serial = _engine(small, large, batch_prefill=False).run(
        make_requests(prompts, 4), 4)
    batched = _engine(small, large, batch_prefill=True).run(
        make_requests(prompts, 4), 4)

    np.testing.assert_array_equal(batched.tokens, serial.tokens)
    np.testing.assert_allclose(batched.confidence, serial.confidence,
                               rtol=1e-5)
    assert batched.stats["prefill_chunks"] == serial.stats["prefill_chunks"]
    assert serial.stats["prefill_dispatches"] == \
        serial.stats["prefill_chunks"]
    assert (batched.stats["prefill_dispatches"]
            < serial.stats["prefill_dispatches"])
    for r in batched.requests:
        t, _ = small.generate(r.prompt[None, :], r.prompt_len, 4)
        np.testing.assert_array_equal(r.tokens[:r.max_new], t[0])


def test_mla_engine_kernel_parity():
    """MLA weight-absorbed kernel decode (compressed paged cache) agrees
    with the gather fallback inside the full engine."""
    key = jax.random.PRNGKey(17)
    cfg = reduced(get_config("deepseek-v2-236b"))
    cfg = cfg.replace(moe=None, family="dense", n_layers=2)
    small = ModelRunner(cfg, tfm.init_params(cfg, key))
    large = ModelRunner(cfg.replace(name="l"),
                        tfm.init_params(cfg, jax.random.fold_in(key, 1)))
    prompts = ragged_prompts(jax.random.fold_in(key, 2), [6, 9, 4, 7],
                             cfg.vocab_size)
    res_fb = _engine(small, large, paged_kernel=False).run(
        make_requests(prompts, 3), 3)
    res_k = _engine(small, large, paged_kernel=True).run(
        make_requests(prompts, 3), 3)
    np.testing.assert_array_equal(res_k.tokens, res_fb.tokens)
    np.testing.assert_allclose(res_k.confidence, res_fb.confidence,
                               rtol=1e-4)
