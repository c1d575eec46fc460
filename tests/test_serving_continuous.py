"""Continuous-batching serving subsystem tests: scheduler invariants,
cache-pool reuse, arrival queue, and static-vs-continuous greedy parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.data.synthetic import make_lm_stream
from repro.models import transformer as tfm
from repro.serving import (ArrivalQueue, CascadeEngine,
                           ContinuousCascadeEngine, ModelRunner, Request,
                           SlotCachePool, SlotScheduler, make_requests)
from repro.serving.request import DONE, RUNNING


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


# ---------------------------------------------------------------------------
# Arrival queue
# ---------------------------------------------------------------------------

def test_arrival_queue_delayed_visibility():
    reqs = [Request(rid=i, prompt=np.zeros(4, np.int32), max_new=2,
                    arrival_time=t) for i, t in enumerate([0.0, 0.5, 0.5, 2.0])]
    q = ArrivalQueue(reqs)
    assert len(q) == 4 and q.n_ready == 0
    q.release(0.0)
    assert q.n_ready == 1
    q.release(1.0)
    assert q.n_ready == 3               # ties released together
    assert q.next_arrival == 2.0
    # FIFO pop order == arrival (and rid for ties)
    assert [q.pop_ready().rid for _ in range(3)] == [0, 1, 2]
    q.release(5.0)
    assert q.pop_ready().rid == 3
    assert len(q) == 0


# ---------------------------------------------------------------------------
# Scheduler: FIFO admission, no slot leaks
# ---------------------------------------------------------------------------

def test_scheduler_fifo_and_no_slot_leaks(tiny_cfg):
    pool = SlotCachePool(tiny_cfg, n_slots=3, max_len=8)
    sched = SlotScheduler(pool)
    reqs = [Request(rid=i, prompt=np.zeros(4, np.int32), max_new=2)
            for i in range(7)]
    q = ArrivalQueue(reqs)

    admitted = sched.admit_ready(q, now=0.0)
    assert [r.rid for _, r in admitted] == [0, 1, 2]        # FIFO
    assert all(r.state == RUNNING for _, r in admitted)
    assert pool.n_free == 0
    sched.check_invariants()
    # pool exhausted: nothing admitted, queue order preserved
    assert sched.admit_ready(q, now=0.0) == []
    assert q.n_ready == 4

    # retire the middle slot; next FIFO request takes exactly that slot
    mid_slot = admitted[1][0]
    r = sched.retire(mid_slot, now=1.0, deferred=False)
    assert r.rid == 1 and r.state == DONE and r.slot is None
    sched.check_invariants()
    (slot, nxt), = sched.admit_ready(q, now=1.0)
    assert nxt.rid == 3 and slot == mid_slot
    sched.check_invariants()

    # drain everything; all slots must come back
    while sched.n_active or len(q):
        for s in list(sched.active_slots):
            sched.retire(s, now=2.0, deferred=bool(s % 2), early=bool(s % 2))
        sched.admit_ready(q, now=2.0)
    sched.check_invariants()
    assert pool.n_free == 3 and sched.n_active == 0
    # double-release must be rejected
    with pytest.raises(RuntimeError):
        pool.release(0)


# ---------------------------------------------------------------------------
# Cache pool: row scatter + reuse across request generations
# ---------------------------------------------------------------------------

def test_cache_pool_scatter_rows_isolated(tiny_cfg):
    pool = SlotCachePool(tiny_cfg, n_slots=4, max_len=8)
    assert jax.tree.structure(pool.cache) == jax.tree.structure(
        pool.batch_axes)

    row = tfm.init_cache(tiny_cfg, 2, 8, dtype=jnp.float32)
    row = jax.tree.map(lambda a: jnp.ones_like(a), row)
    before = jax.tree.map(lambda a: np.asarray(a).copy(), pool.cache)
    pool.write_rows(row, [1, 3])
    for leaf, old, ax in zip(jax.tree.leaves(pool.cache),
                             jax.tree.leaves(before),
                             jax.tree.leaves(pool.batch_axes)):
        leaf = np.moveaxis(np.asarray(leaf), ax, 0)
        old = np.moveaxis(old, ax, 0)
        assert (leaf[1] == 1).all() and (leaf[3] == 1).all()
        np.testing.assert_array_equal(leaf[0], old[0])      # untouched
        np.testing.assert_array_equal(leaf[2], old[2])


def test_cache_pool_slot_reuse_generations(tiny_cfg):
    pool = SlotCachePool(tiny_cfg, n_slots=2, max_len=8)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1}
    with pytest.raises(RuntimeError):
        pool.alloc()
    pool.release(a)
    c = pool.alloc()
    assert c == a                                           # slot reused
    assert pool.generations[a] == 2 and pool.generations[b] == 1


# ---------------------------------------------------------------------------
# Engine: greedy parity + in-flight deferral
# ---------------------------------------------------------------------------

def test_static_continuous_greedy_parity(runners):
    """With early exit disabled the continuous engine must reproduce the
    static cascade token-for-token (greedy), including deferral routing."""
    small, large, prompts = runners
    static = CascadeEngine(small, large)
    tau = static.calibrate(prompts, 8, 4, deferral_ratio=0.5)
    sres = static.serve(prompts, 8, 4)

    cont = ContinuousCascadeEngine(small, large, n_slots=8, tau=tau,
                                   early_exit=False)
    cres = cont.run(make_requests(prompts, 4), 4)
    np.testing.assert_array_equal(cres.tokens, sres.tokens)
    np.testing.assert_array_equal(cres.deferred, sres.deferred)
    np.testing.assert_allclose(cres.confidence, sres.confidence, rtol=1e-6)
    assert cres.saved_steps == 0 and not cres.early_exited.any()


def test_parity_with_slot_reuse(runners):
    """n_slots < n_requests: slots must be recycled across generations
    without contaminating later requests' caches."""
    small, large, prompts = runners
    static = CascadeEngine(small, large, tau=-1e9)          # never defer
    sres = static.serve(prompts, 8, 4)
    cont = ContinuousCascadeEngine(small, large, n_slots=4, tau=-1e9,
                                   early_exit=False)
    cres = cont.run(make_requests(prompts, 4), 4)
    np.testing.assert_array_equal(cres.tokens, sres.tokens)
    assert cres.deferral_ratio == 0.0
    # 16 requests x 3 decode steps on 4 slots => at least 12 engine steps
    assert cres.steps >= 12


def test_parity_with_multi_step_scheduling(runners):
    """steps_per_sync > 1 (chunked decode between host syncs) must not
    change greedy outputs: finished slots self-deactivate on device."""
    small, large, prompts = runners
    static = CascadeEngine(small, large)
    tau = static.calibrate(prompts, 8, 4, deferral_ratio=0.5)
    sres = static.serve(prompts, 8, 4)
    cont = ContinuousCascadeEngine(small, large, n_slots=4, tau=tau,
                                   early_exit=False, steps_per_sync=3)
    cres = cont.run(make_requests(prompts, 4), 4)
    np.testing.assert_array_equal(cres.tokens, sres.tokens)
    np.testing.assert_array_equal(cres.deferred, sres.deferred)


def test_in_flight_deferral_evicts_and_saves(runners):
    """tau above every confidence: every request is evicted at exactly
    min_tokens and regenerated by M_L."""
    small, large, prompts = runners
    cont = ContinuousCascadeEngine(small, large, n_slots=8, tau=1e9,
                                   min_tokens=2, early_exit=True)
    res = cont.run(make_requests(prompts, 4), 4)
    assert res.deferred.all() and res.early_exited.all()
    assert all(r.n_small_steps == 2 for r in res.requests)
    assert res.saved_steps == 16 * (4 - 2)
    assert all(r.state == DONE for r in res.requests)
    # outputs are the large model's generations
    l_tokens, _ = large.generate(prompts, 8, 4)
    np.testing.assert_array_equal(res.tokens, l_tokens)
    # telemetry agrees
    assert res.stats["early_exit_ratio"] == 1.0
    assert res.stats["saved_steps"] == res.saved_steps


def test_calibrated_continuous_run(runners):
    small, large, prompts = runners
    cont = ContinuousCascadeEngine(small, large, n_slots=4, min_tokens=2,
                                   early_exit=True)
    cont.calibrate(prompts, 8, 4, deferral_ratio=0.5)
    res = cont.run(make_requests(prompts, 4), 4)
    assert res.tokens.shape == (16, 4)
    assert 0.2 <= res.deferral_ratio <= 0.9
    assert np.isfinite(res.confidence).all()
    assert res.stats["n_requests"] == 16
    assert res.stats["throughput_tok_s"] > 0


def test_max_new_one(runners):
    """Degenerate budget: the prefill token is the whole generation."""
    small, large, prompts = runners
    cont = ContinuousCascadeEngine(small, large, n_slots=8, tau=-1e9,
                                   early_exit=True)
    res = cont.run(make_requests(prompts, 1), 1)
    s_tokens, _ = small.generate(prompts, 8, 1)
    np.testing.assert_array_equal(res.tokens, s_tokens)
    assert not res.deferred.any()


def test_heterogeneous_max_new_clamped(runners):
    """A request whose max_new exceeds the run budget must still retire
    (regression: unclamped req.max_new made the run loop spin forever)."""
    small, large, prompts = runners
    cont = ContinuousCascadeEngine(small, large, n_slots=4, tau=-1e9,
                                   early_exit=False)
    reqs = make_requests(prompts[:4], 4)
    reqs[0].max_new = 99                    # larger than the run's budget
    reqs[1].max_new = 2                     # smaller: early device stop
    res = cont.run(reqs, 4)
    assert all(r.state == DONE for r in res.requests)
    assert res.requests[0].n_small_steps == 4
    assert res.requests[1].n_small_steps == 2
    s_tokens, _ = small.generate(prompts[:4], 8, 4)
    np.testing.assert_array_equal(res.requests[0].tokens, s_tokens[0])
    np.testing.assert_array_equal(res.requests[1].small_tokens,
                                  s_tokens[1, :2])


def test_mla_family_parity():
    """Vector-position decode must also hold for MLA (compressed-kv cache)."""
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
                                   early_exit=False)
    cres = cont.run(make_requests(prompts, 3), 3)
    np.testing.assert_array_equal(cres.tokens, sres.tokens)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_mellum_paged_engine_matches_reference(kernel):
    """A reduced Mellum (three 16-token-window layers and a YaRN full one,
    2 of 4 experts held), MoE kept, served by the continuous engine on
    the paged backend: chunked prefill, then paged decode (XLA path, or
    the Pallas paged-attention and expert kernels), on prompts past the
    window. Every served token is the plain reference's best at its
    position up to fp32 rounding (a gap of 1e-4 of the largest logit)."""
    from repro.kernels.ref import lm_forward_ref
    key = jax.random.PRNGKey(7)
    cfg = reduced(get_config("mellum2-12b-a2.5b"))
    params = tfm.init_params(cfg, key)
    small = ModelRunner(cfg, params)
    large = ModelRunner(cfg.replace(name="l"), tfm.init_params(
        cfg, jax.random.fold_in(key, 1)))
    lens = [21, 38, 17, 30]
    base = make_lm_stream(jax.random.fold_in(key, 2), len(lens), max(lens),
                          cfg.vocab_size)
    prompts = [base[i, :n].astype(np.int32) for i, n in enumerate(lens)]
    eng = ContinuousCascadeEngine(small, large, n_slots=3, tau=-1e9,
                                  early_exit=False, backend="paged",
                                  block_size=4, prefill_chunk=8,
                                  paged_kernel=kernel)
    res = eng.run(make_requests(prompts, 6), 6)
    assert all(r.state == DONE for r in res.requests)
    for r in res.requests:
        full = np.concatenate([r.prompt, r.tokens[:-1]])
        ref = np.asarray(lm_forward_ref(params, cfg, jnp.asarray(full)))
        at = ref[len(r.prompt) - 1:]
        gap = at.max(-1) - at[np.arange(len(r.tokens)), r.tokens]
        assert gap.max() <= 1e-4 * np.abs(ref).max(), (r.rid, gap)
