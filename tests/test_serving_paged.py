"""Paged KV-cache serving tests: block alloc/free + reservation
invariants, page-table gather vs dense reads, slot-vs-paged greedy
parity, ragged mixed-length admission, chunked prefill, and the
memory-budget regime the slot backend cannot fit."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.data.synthetic import make_lm_stream
from repro.models import transformer as tfm
from repro.models.attention import gather_blocks
from repro.serving import (CascadeEngine, ContinuousCascadeEngine,
                           ModelRunner, PagedCachePool, SlotCachePool,
                           make_requests, poisson_arrivals)
from repro.serving.obs import ObsConfig, Observability
from repro.serving.paged_pool import gather_pages
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
    prompts = make_lm_stream(jax.random.fold_in(key, 2), 16, 8,
                             s_cfg.vocab_size)
    return small, large, prompts


@pytest.fixture(scope="module")
def tiny_cfg():
    return reduced(get_config("internlm2-1.8b"))


def ragged_prompts(key, lens, vocab):
    base = make_lm_stream(key, len(lens), max(lens), vocab)
    return [base[i, :n].astype(np.int32) for i, n in enumerate(lens)]


# ---------------------------------------------------------------------------
# Pool: block alloc/free + reservation invariants
# ---------------------------------------------------------------------------

def test_paged_pool_alloc_free_invariants(tiny_cfg):
    pool = PagedCachePool(tiny_cfg, n_slots=3, n_blocks=8, block_size=4,
                          max_len=20)
    pool.check_invariants()
    assert pool.blocks_for(1) == 1 and pool.blocks_for(4) == 1
    assert pool.blocks_for(5) == 2 and pool.blocks_for(0) == 0

    # admit two requests: reserve worst case, map prompts lazily
    a = pool.alloc()
    pool.reserve(a, 11)                    # 3 blocks owed
    pool.ensure_mapped(a, 6)               # 2 mapped, 1 still owed
    b = pool.alloc()
    pool.reserve(b, 8)                     # 2 blocks owed
    pool.check_invariants()
    assert pool.n_mapped[a] == 2 and pool.n_mapped[b] == 0
    assert pool.n_free_blocks == 6
    # trash block 0 never handed out; mapped ids unique and nonzero
    assert (pool.tables[a, :2] > 0).all()

    # remaining capacity: 6 free - 3 outstanding reserved = 3 blocks
    assert pool.can_reserve(12) and not pool.can_reserve(13)

    # mapping inside the reservation can never fail, even when free
    # would appear exhausted to a naive allocator
    pool.ensure_mapped(a, 11)
    pool.ensure_mapped(b, 8)
    pool.check_invariants()
    assert pool.n_mapped[a] == 3 and pool.n_mapped[b] == 2

    # release returns blocks AND zeroes the table row (stale decode
    # writes from the dead tenant must land in the trash block)
    pool.release(a)
    assert (pool.tables[a] == 0).all()
    assert pool.n_free_blocks == 6
    pool.check_invariants()

    # slot ids recycle lowest-first with generation counters
    c = pool.alloc()
    assert c == a and pool.generations[a] == 2
    with pytest.raises(RuntimeError):
        pool.release(2)                    # slot that is not in use
    pool.release(b)
    pool.release(c)
    pool.check_invariants()
    assert pool.n_free == 3 and pool.n_free_blocks == 8


def test_paged_pool_rejects_unpageable_families(tiny_cfg):
    rwkv = reduced(get_config("rwkv6-3b"))
    with pytest.raises(NotImplementedError):
        PagedCachePool(rwkv, 2, 8, 4, 16)
    windowed = tiny_cfg.replace(sliding_window=8)
    with pytest.raises(NotImplementedError):
        PagedCachePool(windowed, 2, 8, 4, 16)


# ---------------------------------------------------------------------------
# Page-table gather == dense read
# ---------------------------------------------------------------------------

def test_gather_blocks_matches_manual():
    leaf = jnp.arange(5 * 4 * 3, dtype=jnp.float32).reshape(5, 4, 3)
    pages = jnp.asarray([[2, 1], [3, 0]], jnp.int32)
    out = np.asarray(gather_blocks(leaf, pages))
    leaf_np = np.asarray(leaf)
    np.testing.assert_array_equal(out[0],
                                  np.concatenate([leaf_np[2], leaf_np[1]]))
    np.testing.assert_array_equal(out[1],
                                  np.concatenate([leaf_np[3], leaf_np[0]]))


def test_page_table_gather_equals_dense_slot_read(tiny_cfg):
    """Write the same prefilled rows into a dense slot pool and (block by
    block) into a paged pool, whose leaves keep each token's features
    flat; the page-table gather must reproduce the dense per-slot view
    exactly."""
    bs, max_len = 4, 12
    paged = PagedCachePool(tiny_cfg, n_slots=2, n_blocks=6, block_size=bs,
                           max_len=max_len)
    dense = SlotCachePool(tiny_cfg, n_slots=2, max_len=max_len,
                          dtype=jnp.float32)
    for slot in range(2):
        assert paged.alloc() == slot
        paged.reserve(slot, max_len)
        paged.ensure_mapped(slot, max_len)

    row = tfm.init_cache(tiny_cfg, 2, max_len, dtype=jnp.float32)
    row = jax.tree.map(
        lambda a: jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape), row)
    dense.write_rows(row, [0, 1])
    # scatter the same rows block-wise into the paged leaves
    def scatter(paged_leaf, row_leaf, ax):
        assert ax in (0, 1)
        for slot in range(2):
            for m in range(max_len // bs):
                blk = int(paged.tables[slot, m])
                sl_p = (slice(None),) * ax + (blk,)
                sl_r = (slice(None),) * ax + (slot,
                                              slice(m * bs, (m + 1) * bs))
                paged_leaf = paged_leaf.at[sl_p].set(
                    row_leaf[sl_r].reshape(paged_leaf[sl_p].shape))
        return paged_leaf
    paged.cache = jax.tree.map(scatter, paged.cache, row, paged.block_axes)

    view = gather_pages(paged.cache, jnp.asarray(paged.tables),
                        paged.block_axes)
    for g, d, ax in zip(jax.tree.leaves(view), jax.tree.leaves(dense.cache),
                        jax.tree.leaves(dense.batch_axes)):
        d_np = np.moveaxis(np.asarray(d), ax, 0) if ax else np.asarray(d)
        g_np = np.moveaxis(np.asarray(g), ax, 0) if ax else np.asarray(g)
        # gathered view is max_blocks*bs long; valid prefix must match
        g_np = g_np[:, :max_len] if ax == 0 else g_np[:, :, :max_len]
        np.testing.assert_array_equal(g_np.reshape(d_np.shape), d_np)


# ---------------------------------------------------------------------------
# Engine parity: slot vs paged
# ---------------------------------------------------------------------------

def test_uniform_parity_slot_vs_paged(runners):
    """Acceptance: on a uniform workload the paged backend reproduces the
    slot backend (and hence the static cascade) token for token under
    greedy decoding, including deferral routing."""
    small, large, prompts = runners
    static = CascadeEngine(small, large)
    tau = static.calibrate(prompts, 8, 4, deferral_ratio=0.5)
    sres = static.serve(prompts, 8, 4)

    slot = ContinuousCascadeEngine(small, large, n_slots=8, tau=tau,
                                   early_exit=False, backend="slot")
    slot_res = slot.run(make_requests(prompts, 4), 4)
    paged = ContinuousCascadeEngine(small, large, n_slots=8, tau=tau,
                                    early_exit=False, backend="paged",
                                    block_size=4)
    paged_res = paged.run(make_requests(prompts, 4), 4)

    np.testing.assert_array_equal(paged_res.tokens, slot_res.tokens)
    np.testing.assert_array_equal(paged_res.tokens, sres.tokens)
    np.testing.assert_array_equal(paged_res.deferred, sres.deferred)
    np.testing.assert_allclose(paged_res.confidence, slot_res.confidence,
                               rtol=1e-6)
    assert paged_res.stats["backend"] == "paged"
    assert paged_res.stats["peak_blocks"] <= paged_res.stats["n_blocks"]


def test_ragged_parity_vs_single_run(runners):
    """Mixed-length admission on BOTH backends: every request's greedy
    output must equal a standalone single-request generation."""
    small, large, _ = runners
    key = jax.random.PRNGKey(7)
    lens = [5, 9, 4, 12, 7, 6, 10, 4]
    prompts = ragged_prompts(key, lens, small.cfg.vocab_size)
    for backend, kw in (("slot", {}),
                        ("paged", dict(block_size=4, prefill_chunk=4))):
        eng = ContinuousCascadeEngine(small, large, n_slots=3, tau=-1e9,
                                      early_exit=False, backend=backend,
                                      **kw)
        res = eng.run(make_requests(prompts, 5), 5)
        assert all(r.state == DONE for r in res.requests)
        for r in res.requests:
            t, c = small.generate(r.prompt[None, :], r.prompt_len, 5)
            np.testing.assert_array_equal(r.tokens, t[0])
            np.testing.assert_allclose(r.confidence, c[0], rtol=1e-5)


def test_chunked_prefill_does_not_perturb_residents(runners, tmp_path):
    """A long prompt prefilled in chunks while two residents decode must
    leave the residents' tokens AND confidences bit-identical to their
    standalone runs — and the audit log must show the chunked prefill
    actually interleaved with resident decoding."""
    small, large, _ = runners
    key = jax.random.PRNGKey(11)
    prompts = ragged_prompts(key, [6, 6, 14], small.cfg.vocab_size)
    reqs = make_requests(prompts, 10)
    reqs[0].max_new = 4          # retires early, freeing a slot for rid 2
    audit = str(tmp_path / "audit.jsonl")
    eng = ContinuousCascadeEngine(small, large, n_slots=2, tau=-1e9,
                                  early_exit=False, backend="paged",
                                  block_size=4, prefill_chunk=3)
    res = eng.run(reqs, 10, audit_path=audit)
    for r in res.requests:
        t, c = small.generate(r.prompt[None, :], r.prompt_len, r.max_new)
        np.testing.assert_array_equal(r.tokens[:r.max_new], t[0])
        np.testing.assert_allclose(r.confidence, c[0], rtol=1e-5)
    assert res.stats["prefill_chunks"] >= math.ceil(14 / 3) + 2

    events = [json.loads(l) for l in open(audit)]
    kinds = [(e["event"], e.get("rid")) for e in events]
    # rid 2 was admitted only after rid 0 retired, and its chunked
    # prefill finished BEFORE resident rid 1 retired -> interleaved
    assert kinds.index(("retire", 0)) < kinds.index(("prefill_done", 2))
    assert kinds.index(("prefill_done", 2)) < kinds.index(("retire", 1))


def test_paged_serves_budget_slot_cannot_fit(runners):
    """Acceptance: a ragged mixed-length Poisson workload served by the
    paged backend inside a block budget strictly smaller than the slot
    pool's worst-case footprint — with MORE concurrent requests than a
    dense pool of the same byte budget could even hold rows for."""
    small, large, _ = runners
    key = jax.random.PRNGKey(13)
    lens = [4, 4, 4, 4, 4, 4, 4, 4, 10, 4, 4, 4]      # mostly short
    prompts = ragged_prompts(key, lens, small.cfg.vocab_size)
    max_new, bs, n_blocks, n_slots = 4, 4, 12, 6
    max_len = max(lens) + max_new                       # 14

    eng = ContinuousCascadeEngine(small, large, n_slots=n_slots, tau=-1e9,
                                  early_exit=False, backend="paged",
                                  block_size=bs, n_blocks=n_blocks,
                                  prefill_chunk=4)
    arrivals = poisson_arrivals(len(prompts), rate=500.0, seed=13)
    res = eng.run(make_requests(prompts, max_new, arrivals), max_new)
    assert all(r.state == DONE for r in res.requests)
    for r in res.requests:
        t, _ = small.generate(r.prompt[None, :], r.prompt_len, max_new)
        np.testing.assert_array_equal(r.tokens, t[0])

    # paged physical budget (12 blocks of 4 = 48 logical tokens) is far
    # below the slot pool's worst case (6 slots x 14 = 84)
    slot_pool = SlotCachePool(small.cfg, n_slots, max_len)
    assert res.stats["cache_bytes"] < slot_pool.footprint_bytes()
    # a dense pool squeezed into the same token budget affords only
    # 48 // 14 = 3 worst-case rows; the paged run actually sustained more
    dense_affordable = (n_blocks * bs) // max_len
    assert res.stats["peak_active"] > dense_affordable
    assert res.stats["peak_blocks"] <= n_blocks


def test_oversized_request_rejected(runners):
    small, large, _ = runners
    prompts = ragged_prompts(jax.random.PRNGKey(17), [16], 64)
    eng = ContinuousCascadeEngine(small, large, n_slots=2, backend="paged",
                                  block_size=4, n_blocks=2)
    with pytest.raises(ValueError, match="largest request"):
        eng.run(make_requests(prompts, 4), 4)


def test_mla_paged_parity():
    """Paged gather/scatter must also hold for the MLA compressed-kv
    cache (ckv + rope-key leaves page independently of head count)."""
    key = jax.random.PRNGKey(3)
    cfg = reduced(get_config("deepseek-v2-236b"))
    cfg = cfg.replace(moe=None, family="dense", n_layers=2)
    small = ModelRunner(cfg, tfm.init_params(cfg, key))
    large = ModelRunner(cfg.replace(name="l"), tfm.init_params(
        cfg, jax.random.fold_in(key, 1)))
    prompts = make_lm_stream(jax.random.fold_in(key, 2), 4, 8,
                             cfg.vocab_size)
    static = CascadeEngine(small, large, tau=-1e9)
    sres = static.serve(prompts, 8, 3)
    cont = ContinuousCascadeEngine(small, large, n_slots=2, tau=-1e9,
                                   early_exit=False, backend="paged",
                                   block_size=4, prefill_chunk=3)
    cres = cont.run(make_requests(prompts, 3), 3)
    np.testing.assert_array_equal(cres.tokens, sres.tokens)


@pytest.mark.parametrize("kernel", [False, True])
def test_paged_step_unrolled_layers_match_scan(tiny_cfg, kernel):
    """With `scan_layers` off the layers run as a Python loop; the paged
    pool still rides through them whole, each layer writing its own
    blocks: one decode step's logits and pool equal the scanned step's
    (to fp32 rounding: the two compile to different fusions)."""
    pool = PagedCachePool(tiny_cfg, n_slots=2, n_blocks=6, block_size=4,
                          max_len=12)
    for slot in range(2):
        pool.alloc()
        pool.reserve(slot, 12)
        pool.ensure_mapped(slot, 9)
    params = tfm.init_params(tiny_cfg, jax.random.PRNGKey(4))
    cache = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(5), a.shape,
                                    a.dtype), pool.cache)
    tok, pos = jnp.asarray([3, 7], jnp.int32), jnp.asarray([8, 5], jnp.int32)
    tables = pool.tables_device()
    out = {}
    for scan in (True, False):
        cfg = tiny_cfg.replace(scan_layers=scan)
        out[scan] = jax.jit(lambda c, cfg=cfg: tfm.decode_step(
            params, cfg, tok, pos, c, ParallelContext(),
            pages=tables, paged_kernel=kernel))(cache)
    np.testing.assert_allclose(np.asarray(out[False][0]),
                               np.asarray(out[True][0]), rtol=1e-5,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(out[False][1]),
                    jax.tree.leaves(out[True][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def test_donated_pool_kernel_parity_and_inplace_count(runners):
    """The decode step and the prefill chunk take the pool donated: greedy
    outputs stay bit-exact between the Pallas and the XLA paths (and with
    standalone generation), and `serving_pool_inplace_total` counts every
    step and prefill dispatch, i.e. XLA honoured each donation."""
    small, large, _ = runners
    prompts = ragged_prompts(jax.random.PRNGKey(19), [5, 9, 4, 12, 7],
                             small.cfg.vocab_size)
    out = {}
    for kernel in (False, True):
        eng = ContinuousCascadeEngine(small, large, n_slots=3, tau=-1e9,
                                      early_exit=False, backend="paged",
                                      block_size=4, prefill_chunk=4,
                                      steps_per_sync=2, paged_kernel=kernel)
        obs = Observability(ObsConfig())
        res = eng.run(make_requests(prompts, 5), 5, obs=obs)
        inplace = obs.registry.snapshot()["serving_pool_inplace_total"]
        obs.finish()
        n_steps = res.steps // eng.steps_per_sync
        assert n_steps > 0 and res.stats["prefill_dispatches"] > 0
        assert inplace == {("prefill",): res.stats["prefill_dispatches"],
                           ("step",): n_steps}
        assert res.stats["pool_inplace"] == {
            "step": n_steps, "prefill": res.stats["prefill_dispatches"]}
        out[kernel] = res
    np.testing.assert_array_equal(out[True].tokens, out[False].tokens)
    for r in out[True].requests:
        t, _ = small.generate(r.prompt[None, :], r.prompt_len, 5)
        np.testing.assert_array_equal(r.tokens, t[0])


# ---------------------------------------------------------------------------
# run() signature: prompt_len removed
# ---------------------------------------------------------------------------

def test_run_prompt_len_removed(runners):
    small, large, prompts = runners
    eng = ContinuousCascadeEngine(small, large, n_slots=2)
    reqs = make_requests(prompts[:2], 4)
    with pytest.raises(TypeError, match="prompt_len"):
        eng.run(reqs, 8, 4)                 # old positional call shape
    with pytest.raises(TypeError, match="prompt_len"):
        eng.run(reqs, prompt_len=8)
    with pytest.raises(ValueError, match="prompt_len"):
        eng.serve(prompts[:2], 99, 4)       # mismatched width
