#!/usr/bin/env python3
"""Serve the Gatekeeper cascade once on one TPU chip at published widths.

    python3 chip_smoke.py [--seed N]

Run from the root of a checkout, in one process: a TPU chip admits one
process at a time, so the top tier (M_L) is served in-process
(`--large-backend sync`). Phases, each of which fails the run:

  1. serve — `repro.launch.serve.main` builds the two-tier ladder at
     internlm2-1.8b's published widths with bf16 params (tier 0 as
     published; the top tier derived from it by `build_ladder`),
     calibrates tau for a 0.3 deferral ratio and serves 16 ragged
     requests (prompts 64-256 tokens, 16 new tokens each) through the
     continuous engine over the paged KV cache, with the Pallas paged
     kernels on and 16-token blocks. Every request must either finish
     its budget on tier 0 or be deferred and regenerated by the top tier.
  2. kernel on the path — the engine's jitted paged decode step is
     lowered and compiled; its compiled text must hold `tpu_custom_call`.
  3. kernel vs XLA — from one prefilled paged cache, one decode step's
     logits through the Pallas kernels and through the XLA gather path
     (`paged_kernel=False`) must agree within `LOGITS_RTOL` (see there).

Weights and data are generated from `--seed`; nothing outside the
committed files is read. Compile counts and seconds and the device's peak
memory are printed as set-up observations, not as benchmark numbers. The
last line of stdout is a JSON object naming the device, printed only when
every phase passed; with no TPU (or outside a checkout) the script exits
non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "internlm2-1.8b"
MAX_NEW = 16
SERVE_ARGV = [
    "--arch", ARCH, "--widths", "published",
    "--engine", "continuous", "--backend", "paged", "--paged-kernel", "on",
    "--block-size", "16", "--prefill-chunk", "64", "--slots", "8",
    "--requests", "16", "--ragged-min", "64", "--ragged-max", "256",
    "--max-new", str(MAX_NEW), "--deferral-ratio", "0.3",
    "--large-backend", "sync",
]

# Kernel-vs-XLA logits tolerance, relative to the largest reference logit.
# Both paths write bit-identical K/V (pinned by tests/test_paged_kernel.py)
# and differ only inside attention: the kernel keeps the softmax weights
# in fp32 and accumulates online, the XLA path rounds them to bf16 before
# the PV product. Each layer's attention output can therefore move by
# about one bf16 rounding (2^-8 = 0.0039 relative); the residual stream
# carries such moves from every layer forward, and the final norm and
# unembedding map them onto the logits. At these widths, with random
# weights, the gap measured 0.0067 of the largest logit with 1 layer,
# 0.0124 with 4 and 0.0136 with 8 (CPU, kernels in interpret mode): it
# grows slower than the depth. 0.05 leaves room for 24 layers, while a
# wrong block, mask or position moves logits by O(1) of their scale.
LOGITS_RTOL = 0.05


def require(cond, what):
    """A check that stays under `python -O`."""
    if not cond:
        raise RuntimeError(f"chip_smoke.py check failed: {what}")


class Compiles:
    """Counts backend compiles (persistent-cache loads included) and their
    seconds, through JAX's monitoring events."""

    def __init__(self, jax):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"{self.n} compiles, {self.seconds:.1f} s, "
                f"{self.cache_hits} persistent-cache hits")


def serve_phase(argv):
    """Phase 1: serve through the CLI entry point; returns (engine, res)
    after checking every request's outcome."""
    from repro.launch import serve
    from repro.serving.request import DONE

    engine, res = serve.main(argv)
    cfg, n_tiers = engine.small.cfg, engine.spec.n_tiers
    top = n_tiers - 1
    require(res.stats["paged_kernel"], "paged kernel on")
    kept = deferred = 0
    for r in res.requests:
        require(r.state == DONE, (r.rid, r.state))
        tok = np.asarray(r.tokens)
        require(tok.shape == (r.max_new,), (r.rid, tok.shape))
        require(((tok >= 0) & (tok < cfg.vocab_size)).all(),
                (r.rid, "tokens in vocabulary"))
        if r.deferred:
            require(r.tier == top, (r.rid, r.tier))
            require(r.n_small_steps <= r.max_new, (r.rid, r.n_small_steps))
            deferred += 1
        else:
            require(r.tier == 0, (r.rid, r.tier))
            require(r.n_small_steps == r.max_new, (r.rid, r.n_small_steps))
            np.testing.assert_array_equal(tok, r.small_tokens)
            kept += 1
    print(f"served {len(res.requests)} requests: {kept} finished on tier 0, "
          f"{deferred} deferred and regenerated by tier {top} "
          f"({res.stats['tier_names'][top]})")
    require(kept and deferred, "both tiers served requests")
    return engine, res


def paged_step(engine):
    """The engine's jitted paged decode step (built during phase 1)."""
    fns = [fns for key, fns in engine._fns.items() if key[0] == "paged"]
    require(len(fns) == 1, ("one paged program set", list(engine._fns)))
    return fns[0][2]


def prefilled(runner, block_size, seed, lens=(64, 100, 177, 255)):
    """One paged cache holding a prefilled prompt per length in `lens`
    (ragged, crossing block boundaries), each row mapped for one decode
    position past its prompt: the state both checks below decode from.
    Returns (cache, page tables, next tokens, decode positions)."""
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import make_lm_stream
    from repro.models import transformer as tfm
    from repro.serving.paged_pool import PagedCachePool

    cfg = runner.cfg
    max_len = max(lens) + 1
    n = len(lens)
    pool = PagedCachePool(cfg, n, n * -(-max_len // block_size), block_size,
                          max_len)
    for L in lens:
        slot = pool.alloc()
        pool.reserve(slot, L + 1)
        pool.ensure_mapped(slot, L + 1)
    tables = pool.tables_device()
    prompts = make_lm_stream(jax.random.PRNGKey(seed), n, max(lens),
                             cfg.vocab_size)
    last = jnp.asarray([L - 1 for L in lens], jnp.int32)
    # right-padded rows: pad positions past a row's mapped blocks go to
    # the trash block, the one at L is overwritten by the decode write
    logits, cache = jax.jit(
        lambda p, t, c, tb, li: tfm.prefill(p, cfg, t, c, runner.ctx,
                                            pages=tb, last_index=li))(
        runner.params, jnp.asarray(prompts), pool.cache, tables, last)
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    return cache, tables, tok, jnp.asarray(lens, jnp.int32)


def kernel_phase(engine, probe, max_new):
    """Phase 2: the engine's compiled paged decode step, lowered on the
    probe state padded to the engine's slots, calls the Pallas kernels."""
    import jax.numpy as jnp

    cache, tables, tok, pos = probe
    S, n = engine.n_slots, tok.shape[0]
    require(S >= n, (S, n))
    state = {
        "last_tok": jnp.pad(tok, (0, S - n)),
        "pos": jnp.pad(pos, (0, S - n)),
        "n_gen": jnp.ones((S,), jnp.int32),
        "budget": jnp.full((S,), max_new, jnp.int32),
        "conf_sum": jnp.zeros((S,), jnp.float32),
        "active": jnp.arange(S) < n,
        "tokens": jnp.zeros((S, max_new), jnp.int32),
    }
    tables = jnp.pad(tables, ((0, S - n), (0, 0)))
    text = paged_step(engine).lower(engine.small.params, cache, state,
                                    tables).compile().as_text()
    n_calls = text.count('custom_call_target="tpu_custom_call"')
    print(f"paged decode step: {n_calls} tpu_custom_call sites in the "
          f"compiled program")
    require(n_calls > 0, "no Pallas kernel in the compiled decode step")


def logits_phase(runner, probe):
    """Phase 3: one decode step's logits from the probe state, Pallas
    kernels vs XLA gather."""
    import jax

    from repro.models import transformer as tfm

    cache, tables, tok, pos = probe

    def step(kernel):
        return jax.jit(lambda p, t, ps, c, tb: tfm.decode_step(
            p, runner.cfg, t, ps, c, runner.ctx, pages=tb,
            paged_kernel=kernel))(runner.params, tok, pos, cache, tables)[0]

    got = np.asarray(step(True), np.float32)
    ref = np.asarray(step(False), np.float32)
    require(np.isfinite(got).all() and np.isfinite(ref).all(),
            "finite logits")
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    print(f"kernel vs XLA decode logits ({got.shape[0]} rows, vocab "
          f"{got.shape[1]}): max |diff| {err:.6g}, max |ref| {scale:.6g}, "
          f"relative {err / scale:.6g} (bound {LOGITS_RTOL}), argmax "
          f"agrees on {agree}/{got.shape[0]} rows")
    require(err <= LOGITS_RTOL * scale,
            f"logits gap {err} > {LOGITS_RTOL} x {scale}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of weights and traffic")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke.py: no src/repro next to {__file__}; run it "
                 f"from the root of a checkout")
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; devices {devices}; device_kind "
          f"{dev.device_kind!r}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py: JAX found no TPU (platform "
                 f"{dev.platform!r}); this smoke runs on the chip only")

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    compiles = Compiles(jax)

    t0 = time.perf_counter()
    engine, res = serve_phase(SERVE_ARGV + ["--seed", str(args.seed)])
    t1 = time.perf_counter()
    print(f"phase serve: {t1 - t0:.1f} s wall; {compiles.line()}",
          flush=True)
    probe = prefilled(engine.small, engine.block_size, args.seed + 1)
    kernel_phase(engine, probe, MAX_NEW)
    logits_phase(engine.small, probe)
    t2 = time.perf_counter()
    print(f"phases kernel + logits: {t2 - t1:.1f} s wall")

    stats = dev.memory_stats() or {}
    print(f"set-up observations (not benchmark numbers): "
          f"{compiles.line()}; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}; "
          f"total wall {t2 - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
