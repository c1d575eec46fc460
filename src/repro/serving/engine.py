"""Cascade serving engines (paper Fig. 1 deployment: M_S local, M_L remote,
confidence gate g).

Two engines share the same models and calibration:

`CascadeEngine` — the static reference path. Lock-step batches: M_S
prefills + greedy-decodes every request for the full `max_new` tokens
(in a single on-device `fori_loop`, one host transfer per batch), then
requests whose mean eq.-8 negative predictive entropy falls below tau
are regenerated from scratch by M_L.

`ContinuousCascadeEngine` — the continuous-batching serving subsystem.
Requests carry their own prompt lengths (ragged admission); a scheduler
(`scheduler.SlotScheduler`) admits pending requests into free slots every
step and retires finished or deferred ones. The jitted step decodes ALL
slots at once at per-slot positions and accumulates the confidence sum on
device; only tiny per-slot control vectors cross to host each step.
**In-flight deferral**: once a request has decoded `min_tokens` tokens,
a running mean confidence below `tau - margin` evicts it from M_S
immediately — the remaining M_S decode steps are saved — and queues it
for batched M_L regeneration.

Two selectable KV-cache backends (`backend=`):

  * ``"slot"``  — `cache_pool.SlotCachePool`: one dense row of
    `max(prompt_len + max_new)` positions per slot, allocated once.
    Ragged prompts are admitted in per-length groups (batched prefill per
    distinct length); every slot pays the worst-case row.
  * ``"paged"`` — `paged_pool.PagedCachePool`: fixed-size blocks + a
    per-slot page table; blocks are mapped on demand as each request's
    frontier advances and freed at retirement, so a short request never
    pays for the longest one. Long prompts prefill in fixed-size chunks
    (`prefill_chunk`) interleaved with decode steps, so a long arrival
    never stalls resident requests' decoding.

Parity guarantees (pinned by tests): with `early_exit=False` the
continuous engine is token-for-token identical to the static engine
under greedy decoding on uniform workloads, for BOTH backends; on ragged
workloads each request's greedy tokens equal a standalone
`ModelRunner.generate` run of that single request.

Metrics mirror the paper (deferral ratio, per-request confidence,
cost_small + r * cost_large) plus serving telemetry (tokens/s, latency
percentiles, early-exit savings, cache footprint) from
`telemetry.ServingTelemetry`.
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.core.calibration import (calibrate_edges, expected_compute_cost,
                                    ladder_compute_cost)
from repro.core.cascade_spec import CascadeSpec
from repro.core.deferral import SignalObservation
from repro.core.recalibration import EdgeRecalibrator
from repro.kernels import ops as kernel_ops
from repro.models import transformer as tfm
from repro.serving.cache_pool import (SlotCachePool, cache_batch_axes,
                                      scatter_rows)
from repro.serving.config import (LEGACY_KWARG_MAP, MIGRATION_HINT,
                                  EngineConfig, MLBackendConfig, PagedConfig)
from repro.serving.large_backend import make_large_backend
from repro.serving.obs import Observability
from repro.serving.obs.trace import emit_request_spans
from repro.serving.paged_pool import (BlockPressure, PagedCachePool,
                                      next_pow2)
from repro.serving.pressure import (DEFER, PREEMPT, SHED,
                                    make_pressure_policy)
from repro.serving.request import (DEFERRED_PENDING, DONE, EXPIRED,
                                   REJECTED, ArrivalQueue, Request,
                                   make_requests)
from repro.serving.scheduler import SlotScheduler
from repro.serving.telemetry import ServingTelemetry
from repro.sharding import ParallelContext


def _neg_entropy(logits: jnp.ndarray) -> jnp.ndarray:
    """Eq. 8 confidence: negative predictive entropy, computed in fp32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.sum(jnp.exp(logp) * logp, axis=-1)


def _zero_moe(state: dict) -> dict:
    """A dispatch's held-expert counters start from zero (dense configs
    carry none)."""
    if "moe" not in state:
        return state
    return {**state, "moe": jnp.zeros_like(state["moe"])}


@dataclasses.dataclass
class ServeResult:
    """Static-engine output batch (rid order == input row order)."""
    tokens: np.ndarray            # [B, max_new] final (post-cascade) tokens
    small_tokens: np.ndarray
    confidence: np.ndarray        # [B] mean per-step neg entropy (eq. 8)
    deferred: np.ndarray          # [B] bool
    deferral_ratio: float
    compute_cost: float
    steps: int


class ModelRunner:
    """Jit-compiled prefill + decode for one model.

    `generate` runs the whole greedy loop on device (`lax.fori_loop` over
    decode steps, tokens accumulated into a preallocated buffer) and
    transfers the token matrix + confidence vector to host ONCE. Its
    program is named `ml_generate` (`jit_ml_generate` in a profiler
    trace), `sample`'s `ml_sample`, whatever the shape.
    """

    def __init__(self, cfg: ModelConfig, params: Any,
                 ctx: Optional[ParallelContext] = None,
                 max_len: int = 512):
        self.cfg = cfg
        self.params = params
        self.ctx = ctx or ParallelContext()
        self.max_len = max_len
        self._gen_fns: Dict[Tuple[int, int], Any] = {}

    def _generate_impl(self, params, prompts, *, prompt_len: int,
                       max_new: int):
        cfg, ctx = self.cfg, self.ctx
        B = prompts.shape[0]
        cache = tfm.init_cache(cfg, B, prompt_len + max_new,
                               dtype=cfg.cdtype())
        logits, cache = tfm.prefill(params, cfg, prompts, cache, ctx,
                                    last_only=True)
        last = logits[:, -1, :]
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        conf_sum = _neg_entropy(last)
        buf = jnp.zeros((B, max_new), jnp.int32).at[:, 0].set(tok)

        def body(i, carry):
            tok, conf_sum, cache, buf = carry
            step_logits, cache = tfm.decode_step(params, cfg, tok,
                                                 prompt_len + i, cache, ctx)
            tok = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
            conf_sum = conf_sum + _neg_entropy(step_logits)
            buf = buf.at[:, i + 1].set(tok)
            return tok, conf_sum, cache, buf

        _, conf_sum, _, buf = jax.lax.fori_loop(
            0, max_new - 1, body, (tok, conf_sum, cache, buf))
        return buf, conf_sum / max_new

    def generate(self, prompts: np.ndarray, prompt_len: int,
                 max_new: int) -> Tuple[np.ndarray, np.ndarray]:
        """Greedy generation. prompts [B, prompt_len]. Returns
        (tokens [B, max_new], mean_neg_entropy [B]) — one device->host
        transfer for the whole batch."""
        key = (prompt_len, max_new)
        fn = self._gen_fns.get(key)
        if fn is None:
            def ml_generate(params, prompts):
                return self._generate_impl(params, prompts,
                                           prompt_len=prompt_len,
                                           max_new=max_new)
            fn = self._gen_fns[key] = jax.jit(ml_generate)
        tokens, conf = fn(self.params, jnp.asarray(prompts))
        return np.asarray(tokens), np.asarray(conf)

    def _sample_impl(self, params, prompts, seed, *, prompt_len: int,
                     max_new: int, temperature: float):
        cfg, ctx = self.cfg, self.ctx
        B = prompts.shape[0]
        key = jax.random.PRNGKey(seed)
        cache = tfm.init_cache(cfg, B, prompt_len + max_new,
                               dtype=cfg.cdtype())
        logits, cache = tfm.prefill(params, cfg, prompts, cache, ctx,
                                    last_only=True)
        inv_t = 1.0 / temperature
        tok = jax.random.categorical(
            jax.random.fold_in(key, 0),
            logits[:, -1, :].astype(jnp.float32) * inv_t,
            axis=-1).astype(jnp.int32)
        buf = jnp.zeros((B, max_new), jnp.int32).at[:, 0].set(tok)

        def body(i, carry):
            tok, cache, buf = carry
            step_logits, cache = tfm.decode_step(params, cfg, tok,
                                                 prompt_len + i, cache, ctx)
            tok = jax.random.categorical(
                jax.random.fold_in(key, i + 1),
                step_logits.astype(jnp.float32) * inv_t,
                axis=-1).astype(jnp.int32)
            buf = buf.at[:, i + 1].set(tok)
            return tok, cache, buf

        _, _, buf = jax.lax.fori_loop(0, max_new - 1, body,
                                      (tok, cache, buf))
        return buf

    def sample(self, prompts: np.ndarray, prompt_len: int, max_new: int,
               seed: int = 0, temperature: float = 1.0) -> np.ndarray:
        """Stochastic generation (temperature sampling) for agreement-
        style deferral signals: rows draw independent per-step gumbel
        noise from one run-deterministic PRNG key, so the same
        (prompts, seed) always yields the same samples. Returns
        [B, max_new] int32 tokens."""
        if temperature <= 0:
            raise ValueError(f"temperature must be > 0, got {temperature}")
        key = (prompt_len, max_new, float(temperature), "sample")
        fn = self._gen_fns.get(key)
        if fn is None:
            def ml_sample(params, prompts, seed):
                return self._sample_impl(params, prompts, seed,
                                         prompt_len=prompt_len,
                                         max_new=max_new,
                                         temperature=float(temperature))
            fn = self._gen_fns[key] = jax.jit(ml_sample)
        tokens = fn(self.params, jnp.asarray(prompts),
                    jnp.uint32(seed & 0xFFFFFFFF))
        return np.asarray(tokens)


class CascadeEngine:
    """Two-ModelRunner cascade with a calibrated threshold (static,
    lock-step uniform batches — the reference path)."""

    def __init__(self, small: ModelRunner, large: ModelRunner,
                 tau: float = -1.0, cost_small: float = 0.2,
                 cost_large: float = 1.0):
        self.small = small
        self.large = large
        self.tau = tau
        self.cost_small = cost_small
        self.cost_large = cost_large

    def calibrate(self, val_prompts: np.ndarray, prompt_len: int,
                  max_new: int, deferral_ratio: float) -> float:
        """Pick tau so `deferral_ratio` of the validation prompts fall
        below it, through the repo-wide calibration surface
        (`core.calibration.calibrate_edges`: one quantile rule, one
        ``deferred = conf < tau`` sentinel convention shared with the
        classifier cascade and the N-tier serving ladders)."""
        spec = CascadeSpec.two_tier(self.small, self.large, tau=self.tau,
                                    cost_small=self.cost_small,
                                    cost_large=self.cost_large)
        self.tau = calibrate_edges(spec, val_prompts, max_new=max_new,
                                   deferral_ratio=deferral_ratio,
                                   prompt_len=prompt_len)[0]
        return self.tau

    def serve(self, prompts: np.ndarray, prompt_len: int,
              max_new: int) -> ServeResult:
        """Serve one uniform lock-step batch: full M_S decode, then
        batched M_L regeneration of the below-tau rows."""
        s_tokens, conf = self.small.generate(prompts, prompt_len, max_new)
        deferred = conf < self.tau
        tokens = s_tokens.copy()
        if deferred.any():
            idx = np.nonzero(deferred)[0]
            l_tokens, _ = self.large.generate(prompts[idx], prompt_len,
                                              max_new)
            tokens[idx] = l_tokens
        ratio = float(deferred.mean())
        return ServeResult(
            tokens=tokens, small_tokens=s_tokens, confidence=conf,
            deferred=deferred, deferral_ratio=ratio,
            compute_cost=expected_compute_cost(ratio, self.cost_small,
                                               self.cost_large),
            steps=max_new)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ContinuousServeResult:
    """Continuous-engine output (requests sorted by rid)."""
    requests: List[Request]
    tokens: np.ndarray            # [N, max_new] final tokens, rid order
                                  # (rows with a smaller per-request
                                  # budget are zero-padded; the exact
                                  # vectors are requests[i].tokens)
    confidence: np.ndarray        # [N] mean neg entropy at retirement
    deferred: np.ndarray          # [N] bool
    early_exited: np.ndarray      # [N] bool (in-flight deferrals)
    deferral_ratio: float
    saved_steps: int              # M_S decode steps skipped via early exit
    steps: int                    # engine decode steps executed
    stats: Dict[str, Any]         # telemetry summary


class ContinuousCascadeEngine:
    """Continuous-batching N-tier cascade over a slot or block-paged KV
    pool.

    Constructed from a `core.cascade_spec.CascadeSpec` (the model
    ladder: ordered tiers, per-edge `DeferralEdge(signal, tau, margin,
    min_tokens)` gates) and a `serving.config.EngineConfig` (how to
    execute it: slots, KV backend, M_L batching, optional online tau
    recalibration). Tier 0 runs in the continuous-batching decode loop;
    each edge e hands its deferrals to an execution backend running tier
    e+1, and an intermediate tier's results are gated by the NEXT edge —
    deferred traffic from edge e is arrival traffic for edge e+1,
    through the same submit/poll/flush/drain machinery. A 2-tier spec
    reproduces the original two-model engine bit-exactly; the legacy
    flat-kwargs constructor still works via a deprecation shim
    (`config.LEGACY_KWARG_MAP`).

    With `EngineConfig.recalibration` set, a `core.recalibration
    .EdgeRecalibrator` nudges each edge's tau toward
    `recalib_target` deferral online (EWMA-gated stochastic quantile
    tracking with hysteresis); taus are fixed otherwise.

    Per-slot device state (all [n_slots] unless noted):
      last_tok  — input token for the next decode step
      pos       — absolute decode position (per-slot ragged depths)
      n_gen     — tokens generated so far (prefill token counts as 1)
      budget    — per-slot token budget (request's max_new); a slot
                  self-deactivates on device when n_gen reaches it
      conf_sum  — running eq.-8 negative-entropy sum (ON DEVICE)
      active    — slot currently hosts a decoding request
      tokens    — [n_slots, max_new] output buffer, transferred at retire

    Backends: ``backend="slot"`` preallocates one dense `max_len` cache
    row per slot (uniform worst case); ``backend="paged"`` shares
    `n_blocks` blocks of `block_size` tokens between slots through a page
    table, maps them on demand, and prefills long prompts in
    `prefill_chunk`-token chunks interleaved with resident decode steps.
    Admission is strict FIFO under both; the paged backend additionally
    gates the FIFO head on worst-case block reservation so an admitted
    request can never run out of cache mid-flight (no preemption path).

    Paged hot-path controls:

    * ``paged_kernel`` — True routes paged decode through the Pallas
      paged flash-decode kernels (kernels/paged_attention.py: page-table
      walk in-kernel, no dense gather); False forces the XLA gather
      fallback; None (default) takes the backend default (kernel on
      TPU, fallback on CPU).
    * every decode step slices the page table to the bucketed ACTIVE
      block prefix (`pool.active_prefix_blocks`), so both paths touch
      only `ceil((max_pos + steps_per_sync)/block_size)` blocks per row
      instead of all `max_blocks` — the dominant per-token HBM saving
      when residents are short.
    * ``batch_prefill`` (default True) packs same-offset prefill chunks
      of different mid-prefill requests into ONE `[B_chunk, C]` dispatch
      (per-row page tables + per-row last-index; B_chunk bucketed to a
      power of two with trash-table pad rows), instead of one request
      per engine iteration — at high arrival rates the host dispatch
      count per prompt token drops by ~the batch width. False restores
      the serial one-request-per-iteration loop (parity reference).
    * ``prefix_sharing`` (default True) — admission consults the pool's
      prefix registry: prompt blocks already resident (or cached from a
      retired request) are mapped into the new slot's page table by
      refcount instead of prefilled again, and prefill starts at the
      first unshared token. Blocks stay read-only while shared — every
      write path first runs `pool.ensure_writable`, which copy-on-write
      clones a shared block into a private one, and
      `pool.check_write_disjoint` asserts per dispatch that no physical
      block is writable from two rows (the paged write kernels' safety
      contract). Greedy outputs are bit-exact vs an unshared run.

    M_L regeneration goes through a pluggable `large_backend`
    (``"sync"`` — inline on the decode loop, the reference path;
    ``"thread"`` — a worker thread that overlaps M_L batches with M_S
    decode; ``"stub"`` — the threaded path behind a serialized
    request/response pipe with injectable latency, the shape of a real
    RPC; or a callable factory returning any `LargeBackend` — how the
    distributed socket/replica-pool backends plug in, see
    `serving.remote` and launch/serve.py). Each deferral streams into
    the backend the moment its slot
    retires; completions fold back in every engine iteration. Batch
    shape policy lives in the backend (`large_backend.BatchPolicy`):
    `large_batch=None` batches only at drain, exact-size (bit-identical
    to the static path); an int cuts per-prompt-length batches of that
    size as soon as a group fills, and `large_max_wait` seconds bound
    how long a partial group may wait before flushing padded.

    `steps_per_sync` > 1 enables multi-step scheduling: the jitted step
    runs that many decode steps before the host syncs the control
    vectors, amortizing dispatch overhead. Admission, retirement, and
    eviction then happen at chunk granularity (greedy outputs are
    unchanged — finished slots self-deactivate on device).
    """

    def __init__(self, spec, config: Optional[EngineConfig] = None,
                 **legacy):
        if isinstance(spec, CascadeSpec):
            if legacy:
                raise TypeError(
                    f"ContinuousCascadeEngine(spec, config) takes no extra "
                    f"kwargs, got {sorted(legacy)} — per-edge knobs live on "
                    f"the spec's DeferralEdges, execution knobs on "
                    f"EngineConfig")
            if config is not None and not isinstance(config, EngineConfig):
                raise TypeError(f"config must be an EngineConfig, got "
                                f"{type(config).__name__}")
            self.spec = spec
            self.config = config if config is not None else EngineConfig()
        else:
            # legacy flat-kwargs shim: (small, large, n_slots=..., tau=...,
            # ...) — every old name maps onto a spec/config field
            # (config.LEGACY_KWARG_MAP is the table) so old call sites run
            # through the exact same code path as a hand-built 2-tier spec
            small, large = spec, legacy.pop("large", config)
            if not (hasattr(small, "generate") and hasattr(large, "generate")):
                raise TypeError(
                    "ContinuousCascadeEngine needs a CascadeSpec (plus an "
                    "optional EngineConfig) or the legacy "
                    "(small, large) ModelRunner pair")
            unknown = set(legacy) - set(LEGACY_KWARG_MAP)
            if unknown:
                raise TypeError(f"unknown ContinuousCascadeEngine kwargs "
                                f"{sorted(unknown)}")
            warnings.warn(MIGRATION_HINT, DeprecationWarning, stacklevel=2)
            self.spec = CascadeSpec.two_tier(
                small, large,
                tau=legacy.get("tau", -1.0),
                margin=legacy.get("margin", 0.0),
                min_tokens=legacy.get("min_tokens", 2),
                cost_small=legacy.get("cost_small", 0.2),
                cost_large=legacy.get("cost_large", 1.0))
            self.config = EngineConfig(
                n_slots=legacy.get("n_slots", 8),
                early_exit=legacy.get("early_exit", True),
                steps_per_sync=legacy.get("steps_per_sync", 1),
                backend=legacy.get("backend", "slot"),
                paged=PagedConfig(
                    block_size=legacy.get("block_size", 16),
                    n_blocks=legacy.get("n_blocks"),
                    prefill_chunk=legacy.get("prefill_chunk"),
                    paged_kernel=legacy.get("paged_kernel"),
                    batch_prefill=legacy.get("batch_prefill", True),
                    prefix_sharing=legacy.get("prefix_sharing", True)),
                ml=MLBackendConfig(
                    kind=legacy.get("large_backend", "sync"),
                    large_batch=legacy.get("large_batch"),
                    max_wait=legacy.get("large_max_wait"),
                    stub_latency=legacy.get("stub_latency", 0.0)))
        self._fns: Dict[Tuple, Tuple] = {}

    # -- back-compat attribute surface (the legacy flat-kwarg names read —
    # and where old code mutated them, write — through to spec/config) ----
    @property
    def small(self) -> ModelRunner:
        return self.spec.tiers[0].runner

    @property
    def large(self):
        return self.spec.tiers[1].runner

    @property
    def tau(self) -> float:
        return self.spec.edges[0].tau

    @tau.setter
    def tau(self, v: float) -> None:
        self.spec.edges[0].tau = float(v)

    @property
    def margin(self) -> float:
        return self.spec.edges[0].margin

    @margin.setter
    def margin(self, v: float) -> None:
        self.spec.edges[0].margin = float(v)

    @property
    def min_tokens(self) -> int:
        return self.spec.edges[0].min_tokens

    @min_tokens.setter
    def min_tokens(self, v: int) -> None:
        self.spec.edges[0].min_tokens = max(1, int(v))

    @property
    def early_exit(self) -> bool:
        return self.config.early_exit

    @early_exit.setter
    def early_exit(self, v: bool) -> None:
        self.config.early_exit = bool(v)

    @property
    def n_slots(self) -> int:
        return self.config.n_slots

    @property
    def steps_per_sync(self) -> int:
        return self.config.steps_per_sync

    @property
    def backend(self) -> str:
        return self.config.backend

    @property
    def large_batch(self) -> Optional[int]:
        return self.config.ml.large_batch

    @property
    def large_backend(self):
        return self.config.ml.kind

    @property
    def large_max_wait(self) -> Optional[float]:
        return self.config.ml.max_wait

    @property
    def stub_latency(self) -> float:
        return self.config.ml.stub_latency

    @property
    def block_size(self) -> int:
        return self.config.paged.block_size

    @property
    def n_blocks(self) -> Optional[int]:
        return self.config.paged.n_blocks

    @property
    def prefill_chunk(self) -> Optional[int]:
        return self.config.paged.prefill_chunk

    @property
    def paged_kernel(self) -> Optional[bool]:
        return self.config.paged.paged_kernel

    @property
    def batch_prefill(self) -> bool:
        return self.config.paged.batch_prefill

    @property
    def prefix_sharing(self) -> bool:
        return self.config.paged.prefix_sharing

    @property
    def cost_small(self) -> float:
        return self.spec.tiers[0].cost

    @property
    def cost_large(self) -> float:
        return self.spec.tiers[1].cost

    # -- calibration (the repo-wide Stage-3 surface) -----------------------
    def calibrate(self, val_prompts: np.ndarray, prompt_len: int,
                  max_new: int, deferral_ratio=0.2):
        """Calibrate every edge tau on a uniform validation batch via
        `core.calibration.calibrate_edges` (edge i calibrates on the
        traffic upstream edges would defer that far). Returns the single
        tau for a 2-tier spec (legacy contract) or the per-edge list for
        deeper ladders; `deferral_ratio` may be per-edge."""
        taus = calibrate_edges(self.spec, val_prompts, max_new=max_new,
                               deferral_ratio=deferral_ratio,
                               prompt_len=prompt_len)
        return taus[0] if len(taus) == 1 else taus

    # -- jitted device programs -------------------------------------------
    def _decode_body(self, params, cache, state, pages, max_new,
                     paged_kernel=None):
        """One decode step over ALL slots at per-slot positions; inactive
        slots compute but their state/cache rows are inert. Slots
        self-deactivate when n_gen reaches their budget so multi-step
        chunks never decode past a request's max_new. In paged mode the
        page table rows of inactive slots are masked to the trash block,
        so a stale `pos` from a previous tenant can never scribble into a
        block that now belongs to someone else; `pages` arrives already
        sliced to the bucketed active block prefix, and `paged_kernel`
        picks Pallas flash-decode vs the XLA gather fallback."""
        cfg, ctx = self.small.cfg, self.small.ctx
        n_slots = state["active"].shape[0]
        if pages is not None:
            pages = jnp.where(state["active"][:, None], pages, 0)
        extra = {}
        if "moe" in state:
            # held experts: route live rows only and count, per layer,
            # the held experts hit and the assignments they took
            logits, cache, stats = tfm.decode_step(
                params, cfg, state["last_tok"], state["pos"], cache, ctx,
                pages=pages, paged_kernel=paged_kernel,
                row_mask=state["active"], with_stats=True)
            extra["moe"] = state["moe"] + stats["moe"]
        else:
            logits, cache = tfm.decode_step(params, cfg, state["last_tok"],
                                            state["pos"], cache, ctx,
                                            pages=pages,
                                            paged_kernel=paged_kernel)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        neg_ent = _neg_entropy(logits)
        act = state["active"]
        inc = act.astype(jnp.int32)
        rows = jnp.arange(n_slots)
        col = jnp.clip(state["n_gen"], 0, max_new - 1)
        cur = state["tokens"][rows, col]
        n_gen = state["n_gen"] + inc
        state = {
            "last_tok": jnp.where(act, tok, state["last_tok"]),
            "pos": state["pos"] + inc,
            "n_gen": n_gen,
            "budget": state["budget"],
            "conf_sum": state["conf_sum"] + jnp.where(act, neg_ent, 0.0),
            "active": act & (n_gen < state["budget"]),
            "tokens": state["tokens"].at[rows, col].set(
                jnp.where(act, tok, cur)),
            **extra,
        }
        return cache, state

    def _build_slot_fns(self, max_new: int, pool_len: int):
        """Jitted (admit, step) pair for the dense slot backend. `admit`
        handles one uniform-length group of newly admitted prompts (jit
        re-traces per distinct (group_size, prompt_len) shape)."""
        cfg, ctx = self.small.cfg, self.small.ctx
        batch_axes = cache_batch_axes(cfg, pool_len)

        def admit_fn(params, prompts, slots, budgets, cache, state):
            k, P = prompts.shape
            fresh = tfm.init_cache(cfg, k, pool_len, dtype=cfg.cdtype())
            logits, fresh = tfm.prefill(params, cfg, prompts, fresh, ctx,
                                        last_only=True)
            last = logits[:, -1, :]
            tok0 = jnp.argmax(last, axis=-1).astype(jnp.int32)
            conf0 = _neg_entropy(last)
            cache = scatter_rows(cache, fresh, slots, batch_axes)
            row0 = jnp.zeros((k, max_new), jnp.int32).at[:, 0].set(tok0)
            state = {
                **state,
                "last_tok": state["last_tok"].at[slots].set(tok0),
                "pos": state["pos"].at[slots].set(P),
                "n_gen": state["n_gen"].at[slots].set(1),
                "budget": state["budget"].at[slots].set(budgets),
                "conf_sum": state["conf_sum"].at[slots].set(conf0),
                "active": state["active"].at[slots].set(budgets > 1),
                "tokens": state["tokens"].at[slots].set(row0),
            }
            return cache, state

        def step_fn(params, cache, state):
            state = _zero_moe(state)

            def one(carry, _):
                params, cache, state = carry
                cache, state = self._decode_body(params, cache, state,
                                                 None, max_new)
                return (params, cache, state), None
            (_, cache, state), _ = jax.lax.scan(
                one, (params, cache, state), None,
                length=self.steps_per_sync)
            return cache, state

        return jax.jit(admit_fn), jax.jit(step_fn)

    def _build_paged_fns(self, max_new: int, paged_kernel: bool):
        """Jitted (prefill_chunk, finish, step) triple for the paged
        backend. `prefill_chunk` runs one `[B_chunk, C]` batch of
        same-offset chunks through the trunk at a traced cache offset,
        each row scattering K/V through its own page-table row (serial
        mode is just B_chunk == 1); `finish` seeds a slot's decode state
        from its row's last-real-position logits; `step` mirrors the slot
        backend but routes every cache access through the (active-prefix
        sliced) page table, via Pallas kernels when `paged_kernel`.
        `prefill_chunk` and `step` take the pool's cache donated: XLA
        updates it in place and the caller rebinds `pool.cache` to what
        they return (the old leaves are deleted)."""
        cfg, ctx = self.small.cfg, self.small.ctx

        def prefill_chunk_fn(params, tokens, tables, offset, last_index,
                             cache):
            logits, cache = tfm.prefill(params, cfg, tokens, cache, ctx,
                                        cache_offset=offset, pages=tables,
                                        last_index=last_index)
            return logits[:, 0, :], cache

        def finish_fn(state, slot, logits, budget, prompt_len):
            tok0 = jnp.argmax(logits[0]).astype(jnp.int32)
            conf0 = _neg_entropy(logits)[0]
            row0 = jnp.zeros((max_new,), jnp.int32).at[0].set(tok0)
            return {
                **state,
                "last_tok": state["last_tok"].at[slot].set(tok0),
                "pos": state["pos"].at[slot].set(prompt_len),
                "n_gen": state["n_gen"].at[slot].set(1),
                "budget": state["budget"].at[slot].set(budget),
                "conf_sum": state["conf_sum"].at[slot].set(conf0),
                "active": state["active"].at[slot].set(budget > 1),
                "tokens": state["tokens"].at[slot].set(row0),
            }

        def step_fn(params, cache, state, tables):
            state = _zero_moe(state)

            def one(carry, _):
                params, cache, state = carry
                cache, state = self._decode_body(params, cache, state,
                                                 tables, max_new,
                                                 paged_kernel=paged_kernel)
                return (params, cache, state), None
            (_, cache, state), _ = jax.lax.scan(
                one, (params, cache, state), None,
                length=self.steps_per_sync)
            return cache, state

        def resume_fn(state, slot, last_tok, pos, n_gen, conf_sum, budget,
                      row):
            """Restore a preempted request's decode state verbatim (the
            snapshot taken at preemption) — the counterpart of
            `finish_fn` for re-admission. Continuing from restored state
            over restored/recomputed KV is bit-exact with never having
            been preempted."""
            return {
                **state,
                "last_tok": state["last_tok"].at[slot].set(last_tok),
                "pos": state["pos"].at[slot].set(pos),
                "n_gen": state["n_gen"].at[slot].set(n_gen),
                "budget": state["budget"].at[slot].set(budget),
                "conf_sum": state["conf_sum"].at[slot].set(conf_sum),
                "active": state["active"].at[slot].set(n_gen < budget),
                "tokens": state["tokens"].at[slot].set(row),
            }

        return (jax.jit(prefill_chunk_fn, donate_argnames="cache"),
                jax.jit(finish_fn), jax.jit(step_fn, donate_argnums=(1,)),
                jax.jit(resume_fn))

    # -- host-side control loop -------------------------------------------
    def run(self, requests: List[Request], max_new: Optional[int] = None,
            audit_path: Optional[str] = None, *,
            prompt_len: Optional[int] = None,
            obs=None) -> ContinuousServeResult:
        """Serve `requests` (each carrying its own prompt and budget).

        `max_new` is the run-wide token-buffer width and budget cap
        (default: the largest request budget); per-request `max_new`
        larger than it is clamped so the device budget, retirement check,
        and saved-step accounting agree.

        `obs` selects the observability surface (`repro.serving.obs`):
        ``None`` (default) keeps only the always-on pieces — bounded
        metrics, the compile counter (``stats["compiles"]``), and the
        ``engine.*`` profiler spans that feed phase attribution; an
        `ObsConfig` makes the engine build the
        runtime, run, and export/finish it (the one-shot CLI/bench
        path); a prebuilt `Observability` is caller-owned — the engine
        feeds it but never finishes it (e.g. serve.py keeping the
        /metrics endpoint open across runs). Instrumentation never
        changes what the device computes: greedy outputs are bit-exact
        with observability on or off.

        .. deprecated:: the old ``run(requests, prompt_len, max_new)``
           call shape is gone — prompt lengths are per-request
           (`Request.prompt_len`). Passing `prompt_len` (or the old
           positional layout) raises TypeError.
        """
        if prompt_len is not None:
            raise TypeError(
                "ContinuousCascadeEngine.run() no longer takes prompt_len: "
                "each Request carries its own prompt length "
                "(Request.prompt_len). Call run(requests, max_new) — for "
                "the old uniform behavior just pass uniform-length "
                "prompts to make_requests().")
        if isinstance(audit_path, (int, np.integer)):
            raise TypeError(
                "ContinuousCascadeEngine.run() signature changed: the old "
                "run(requests, prompt_len, max_new) call shape is "
                "deprecated. Prompt lengths are per-request now — call "
                "run(requests, max_new, audit_path=...).")
        if not requests:
            raise ValueError("run() needs at least one request")
        cfg = self.small.cfg
        if max_new is None:
            max_new = max(r.max_new for r in requests)
        for r in requests:
            # a run can never decode past its own max_new; clamp so the
            # device budget, retirement check, and saved-step accounting
            # all agree for heterogeneous requests
            r.max_new = min(r.max_new, max_new)
        max_len = max(r.prompt_len + r.max_new for r in requests)
        paged = self.backend == "paged"

        pressure = self.config.paged.pressure if paged else None
        policy = (make_pressure_policy(pressure.policy,
                                       pressure.max_preemptions)
                  if pressure is not None else None)
        if paged:
            bs = self.block_size
            n_blocks = (self.n_blocks if self.n_blocks is not None
                        else self.n_slots * math.ceil(max_len / bs))
            biggest = max(math.ceil((r.prompt_len + r.max_new - 1) / bs)
                          for r in requests)
            if n_blocks < biggest:
                # each request must fit the PHYSICAL budget on its own:
                # oversubscription stretches the admission (virtual)
                # budget, never physical capacity
                raise ValueError(
                    f"n_blocks={n_blocks} cannot hold the largest request "
                    f"({biggest} blocks of {bs}); raise n_blocks")
            pool = PagedCachePool(
                cfg, self.n_slots, n_blocks, bs, max_len,
                oversubscribe=(pressure.oversubscribe
                               if pressure is not None else 1.0),
                swap_blocks=(pressure.swap_blocks
                             if pressure is not None else 0))
            use_kernel = kernel_ops.paged_kernel_enabled(self.paged_kernel)
            fkey = ("paged", max_new, n_blocks, bs, pool.max_blocks,
                    use_kernel)
            fns = self._fns.get(fkey)
            if fns is None:
                fns = self._build_paged_fns(max_new, use_kernel)
                self._fns[fkey] = fns
            prefill_fn, finish_fn, step_fn, resume_fn = fns
        else:
            pool = SlotCachePool(cfg, self.n_slots, max_len)
            fkey = ("slot", max_new, max_len)
            fns = self._fns.get(fkey)
            if fns is None:
                fns = self._build_slot_fns(max_new, max_len)
                self._fns[fkey] = fns
            admit_fn, step_fn = fns

        sched = SlotScheduler(pool)
        queue = ArrivalQueue(requests, max_queue=self.config.max_queue)
        # engine-level deadline default; an explicit per-request deadline
        # (e.g. from make_requests(deadline_s=...)) wins
        if self.config.deadline_s is not None:
            for r in requests:
                if r.deadline is None:
                    r.deadline = r.arrival_time + self.config.deadline_s
        overload_on = (queue.max_queue is not None
                       or any(r.deadline is not None for r in requests))
        # a passed-in Observability is caller-owned; anything else
        # (None or an ObsConfig) the engine builds and finishes itself
        own_obs = not isinstance(obs, Observability)
        obs_rt = obs if isinstance(obs, Observability) else Observability(obs)
        if own_obs:
            obs_rt.start_server()
        tr = obs_rt.tracer
        dev_timer = obs_rt.device_timer
        profiler = obs_rt.profiler
        # the audit-log handle must be released even when setup or the
        # serve loop raises: ServingTelemetry is a context manager, and
        # the worker backend gets its own try/finally inside (a leaked
        # worker thread spins its poll loop for the life of the process)
        tel = ServingTelemetry(audit_path, obs=obs_rt)
        # engine.<name> spans: profiler annotations, phase attribution,
        # and the Chrome engine track when tracing is on
        span = obs_rt.spans(lambda: tel.now, tel.phase_add)
        spec = self.spec
        n_edges = len(spec.edges)
        last_tier = spec.n_tiers - 1
        edge0 = spec.edges[0]
        # online tau recalibration: one controller per edge, seeded from
        # the configured (offline) taus; None = fixed taus, the
        # parity-pinned default
        recal = None
        if self.config.recalibration is not None:
            recal = EdgeRecalibrator(list(spec.taus),
                                     self.config.recalib_target,
                                     self.config.recalibration)

        def edge_tau(e: int) -> float:
            return recal.tau(e) if recal is not None else spec.edges[e].tau

        backends: List[Any] = []
        compiles = obs_rt.count_compiles()
        try:
            S = self.n_slots
            state = {
                "last_tok": jnp.zeros((S,), jnp.int32),
                "pos": jnp.zeros((S,), jnp.int32),
                "n_gen": jnp.zeros((S,), jnp.int32),
                "budget": jnp.full((S,), max_new, jnp.int32),
                "conf_sum": jnp.zeros((S,), jnp.float32),
                "active": jnp.zeros((S,), bool),
                "tokens": jnp.zeros((S, max_new), jnp.int32),
            }
            if tfm.held_experts(self.small.cfg):
                # per-layer held-expert counters of the last dispatch
                state["moe"] = jnp.zeros((self.small.cfg.n_layers
                                          - self.small.cfg.moe.n_dense_layers,
                                          2), jnp.int32)
            # paged: requests admitted to a slot but still prefilling,
            # FIFO of [request, slot, next chunk offset]
            prefilling: List[List] = []
            n_steps = 0
            moe_totals = [0, 0]     # held experts hit, assignments taken
            # the window of each windowed layer (decode span args)
            kinds = self.small.cfg.layer_kinds
            windows = [kinds[i % len(kinds)].window
                       for i in range(self.small.cfg.n_layers)] if kinds else []
            windows = [w for w in windows if w]
            n_prefill_chunks = 0
            n_prefill_dispatches = 0
            n_prefill_tokens = 0
            n_shared_tokens = 0
            peak_active = 0
            # memory-pressure accounting (oversubscribed paged runs)
            n_oom_defers = 0
            n_relief = 0
            # one execution backend per edge: backends[e] runs tier e+1.
            # A tier's own `backend` wins; otherwise config.ml.kind.
            # Only edge 0's backend registers metrics (the registry's
            # metric names are unique per run; edge 0 is the legacy
            # surface the dashboards already chart).
            cfg_ml = self.config.ml
            for e in range(n_edges):
                tier = spec.tiers[e + 1]
                kind = tier.backend if tier.backend is not None \
                    else cfg_ml.kind
                backends.append(make_large_backend(
                    kind, tier.runner, max_new,
                    cfg_ml.large_batch, cfg_ml.max_wait,
                    cfg_ml.stub_latency,
                    registry=tel.registry if e == 0 else None))
            ml = backends[0]
            by_rid = {r.rid: r for r in requests}
            ml_depths: List[int] = []
            # pull-mode gauges: evaluated only when someone scrapes
            # /metrics or renders the registry — zero loop cost
            reg = tel.registry
            reg.gauge("serving_active_slots",
                      "requests resident in M_S decode slots",
                      fn=lambda: sched.n_active)
            reg.gauge("serving_queue_ready",
                      "arrived requests awaiting slot admission",
                      fn=lambda: queue.n_ready)
            reg.gauge("serving_requests_admitted",
                      "requests admitted into slots (lifetime)",
                      fn=lambda: sched.n_admitted)
            reg.gauge("serving_requests_retired",
                      "requests retired from slots (lifetime)",
                      fn=lambda: sched.n_retired)
            reg.gauge("serving_preemptions",
                      "requests preempted under block pressure (lifetime)",
                      fn=lambda: sched.n_preempted)
            if paged:
                pool.register_metrics(reg)
            # host mirrors of the device confidence accumulators, used
            # only when span tracing is on to derive the per-token
            # confidence record from per-sync deltas of conf_sum
            conf_prev = np.zeros(S, np.float64)
            ngen_prev = np.zeros(S, np.int64)
            tel.reset_clock()

            edge_deferrals = [0] * n_edges

            def submit_large(req: Request, edge: int):
                """Stream one deferral across `edge` into tier edge+1's
                backend the moment the upstream tier lets go of it — the
                rest of the ladder keeps working while that tier
                regenerates."""
                edge_deferrals[edge] += 1
                req.tier = edge + 1
                req.state = DEFERRED_PENDING
                req.t_submit_large = tel.now
                backends[edge].submit([req])
                tel.event("large_submit", rid=req.rid, edge=edge,
                          depth=backends[edge].n_pending)

            def total_pending() -> int:
                return sum(b.n_pending for b in backends)

            def poll_large():
                """Fold completed regenerations back into the run. A
                result from backends[e] is tier e+1's output: at the last
                tier it is final; at an intermediate tier it is gated by
                edge e+1 — below tau it becomes arrival traffic for the
                next backend, above it the request retires here."""
                for e, be in enumerate(backends):
                    for res in be.poll():
                        req = by_rid[res.rid]
                        tier = e + 1
                        now = tel.now
                        if tier < last_tier:
                            edge = spec.edges[tier]
                            sig = edge.signal
                            if sig.supports_running:
                                conf = float(res.confidence)
                            else:
                                conf = float(sig.finalize(SignalObservation(
                                    prompt=req.prompt,
                                    mean_confidence=float(res.confidence),
                                    tokens=np.asarray(res.tokens, np.int32),
                                    runner=spec.tiers[tier].runner,
                                    max_new=max_new, rid=req.rid)))
                            tau_e = edge_tau(tier)
                            defer = conf < tau_e
                            if recal is not None:
                                recal.observe(tier, conf)
                            tel.event("tier_gate", rid=req.rid, tier=tier,
                                      edge=tier, confidence=round(conf, 6),
                                      tau=round(tau_e, 6), deferred=defer)
                            if defer:
                                submit_large(req, tier)
                                continue
                        # accepted at this tier: final tokens, trimmed to
                        # the request's own budget (backends pad
                        # generation width to the run-wide max_new)
                        req.tier = tier
                        req.tokens = np.asarray(
                            res.tokens, np.int32)[:req.max_new].copy()
                        req.state = DONE
                        req.t_done = now
                        tel.m_tokens.labels(model="large").inc(
                            len(req.tokens))
                        tel.event("large_complete", rid=req.rid, tier=tier,
                                  batch_id=res.batch_id, n_real=res.n_real,
                                  pad_to=res.pad_to, reason=res.reason,
                                  wait_ms=round(
                                      (now - req.t_submit_large) * 1e3, 3))

            def sync_retire(step: Optional[int] = None):
                """Pull the tiny control vectors, retire finished /
                in-flight deferred slots, release them, and deactivate on
                device. Slots still prefilling are skipped — their device
                state is stale until the final chunk seeds it. After a
                decode dispatch (`step`) of a held-expert model the same
                pull brings its per-layer expert counters, written to the
                audit log under the step's index."""
                moe = step is not None and "moe" in state
                with span("sync"):
                    pulled = jax.device_get(
                        (state["n_gen"], state["conf_sum"])
                        + ((state["moe"],) if moe else ()))
                if moe:
                    hit, assign = pulled[2][:, 0], pulled[2][:, 1]
                    moe_totals[0] += int(hit.sum())
                    moe_totals[1] += int(assign.sum())
                    tel.m_moe_assign.inc(int(assign.sum()))
                    tel.event("moe", step=step, hit=hit.tolist(),
                              assign=assign.tolist())
                with span("retire"):
                    retire(pulled[0], pulled[1])

            def retire(n_gen, conf_sum):
                """Host side of `sync_retire`: gate each decoding slot on
                its pulled counts, release the ones leaving, and hand
                deferrals to the next tier's backend."""
                nonlocal state
                mid_prefill = {s for _, s, _ in prefilling}
                toks = None
                retired: List[int] = []
                now = tel.now
                sig0 = edge0.signal
                for slot in sched.active_slots:
                    if slot in mid_prefill:
                        continue
                    req = sched.running[slot]
                    n = int(n_gen[slot])
                    mean = float(conf_sum[slot]) / max(n, 1)
                    finished = n >= req.max_new
                    tau0 = edge_tau(0)
                    # in-flight deferral needs a running form of the
                    # signal; signals without one (k-sample agreement)
                    # can only gate at full retirement
                    evict = (not finished and self.early_exit
                             and sig0.supports_running
                             and n >= edge0.min_tokens
                             and sig0.running(mean, n) < tau0 - edge0.margin)
                    if not (finished or evict):
                        continue
                    if toks is None:
                        with span("sync"):
                            toks = np.asarray(state["tokens"])
                    req.n_small_steps = n
                    req.small_tokens = toks[slot, :n].copy()
                    if evict:
                        conf, defer = mean, True
                    else:
                        conf = (mean if sig0.supports_running
                                else float(sig0.finalize(SignalObservation(
                                    prompt=req.prompt, mean_confidence=mean,
                                    tokens=req.small_tokens,
                                    runner=self.small, max_new=req.max_new,
                                    rid=req.rid))))
                        defer = conf < tau0
                    req.confidence = conf
                    if recal is not None:
                        recal.observe(0, conf)
                    sched.retire(slot, now, deferred=defer, early=evict)
                    if defer:
                        submit_large(req, 0)
                    else:
                        req.tokens = toks[slot, :req.max_new].copy()
                    reason = ("defer_early" if evict else
                              "defer_final" if defer else "finish")
                    tel.event("retire", rid=req.rid, slot=slot,
                              reason=reason, n_gen=n,
                              confidence=round(mean, 6))
                    tel.m_requests.labels(outcome=reason).inc()
                    if not defer:
                        tel.m_tokens.labels(model="small").inc(
                            len(req.tokens))
                    retired.append(slot)
                if retired:
                    state = dict(state)
                    state["active"] = state["active"].at[
                        jnp.asarray(retired)].set(False)

            def seed_conf_trace(pairs):
                """Start each newly decoding request's per-token
                confidence record from its prefill seed value (tracing
                mode only; one transfer for the whole batch)."""
                cs = np.asarray(state["conf_sum"])
                ng = np.asarray(state["n_gen"])
                for slot, req in pairs:
                    conf_prev[slot] = float(cs[slot])
                    ngen_prev[slot] = int(ng[slot])
                    req.conf_trace = [round(conf_prev[slot], 6)]

            def record_conf_trace(decoding):
                """Extend the per-token confidence records from per-sync
                deltas of the device-accumulated conf_sum (tracing mode
                only — sync_retire transfers these vectors right after,
                so no extra device work is forced; with steps_per_sync>1
                each entry is the chunk's mean)."""
                cs, ng = jax.device_get((state["conf_sum"],
                                         state["n_gen"]))
                for slot in decoding:
                    req = sched.running[slot]
                    dn = int(ng[slot]) - int(ngen_prev[slot])
                    if req.conf_trace is not None and dn > 0:
                        req.conf_trace.append(round(
                            (float(cs[slot]) - conf_prev[slot]) / dn, 6))
                    conf_prev[slot] = float(cs[slot])
                    ngen_prev[slot] = int(ng[slot])

            def admit_slot_groups(admitted):
                """Slot backend: batched prefill per distinct prompt length
                (mixed lengths can't share one dense prefill shape;
                grouping keeps each group's math identical to a uniform
                run)."""
                nonlocal state
                by_len: Dict[int, List[Tuple[int, Request]]] = {}
                for s, r in admitted:
                    by_len.setdefault(r.prompt_len, []).append((s, r))
                for P, group in sorted(by_len.items()):
                    slots = jnp.asarray([s for s, _ in group])
                    prompts = jnp.asarray(np.stack([r.prompt
                                                    for _, r in group]))
                    budgets = jnp.asarray([r.max_new for _, r in group],
                                          jnp.int32)
                    pool.cache, state = admit_fn(self.small.params, prompts,
                                                 slots, budgets, pool.cache,
                                                 state)
                now = tel.now
                for _, r in admitted:
                    r.t_prefill_done = now
                if tr is not None:
                    seed_conf_trace(admitted)
                tel.event("admit", rids=[r.rid for _, r in admitted],
                          slots=[s for s, _ in admitted])
                sync_retire()       # min_tokens=1 / max_new=1 edges

            def run_prefill_chunk():
                """Paged backend: run one chunk of the oldest mid-prefill
                request — PLUS, with `batch_prefill`, the same-offset
                chunks of every other mid-prefill request — in a single
                dispatch, so long prompts interleave with resident decode
                steps and simultaneous arrivals don't serialize on host
                overhead. Under oversubscription the chunk's CoW clones
                can hit BlockPressure; pressure strikes before the
                dispatch mutates anything, so after a policy eviction
                (which may remove mid-prefill entries) the whole chunk
                simply restarts against the survivors."""
                while prefilling:
                    try:
                        return _prefill_chunk_once()
                    except BlockPressure:
                        if policy is None or not relieve_pressure():
                            raise

            def _prefill_chunk_once():
                """One prefill dispatch. Before it, every row's chunk
                span is made write-private (`ensure_writable` CoW-clones
                a shared tail block) and the rows' writable blocks are
                asserted pairwise disjoint — the paged write paths'
                contract. A resumed request's chunks stop at
                `prefill_end` (its decode-written tail is restored from
                the preemption snapshot instead of recomputed)."""
                nonlocal state, n_prefill_chunks, n_prefill_dispatches, \
                    n_prefill_tokens
                head_req, _, off0 = prefilling[0]
                C = self.prefill_chunk or (prefill_end(head_req) - off0)
                if self.batch_prefill:
                    # pack every request at the head's offset whose chunk
                    # width matches (differing widths only arise with
                    # prefill_chunk=None, where C spans the whole
                    # unshared prompt tail)
                    group = [e for e in prefilling if e[2] == off0
                             and (self.prefill_chunk
                                  or prefill_end(e[0]) - e[2]) == C]
                else:
                    group = [prefilling[0]]
                k = len(group)
                for req, slot, off in group:
                    pool.ensure_writable(slot, off, off + C)
                pool.check_write_disjoint(
                    (slot, off, off + C) for _, slot, off in group)
                # bucket the dispatch width to a power of two: pad rows
                # write to the trash block, their logits are ignored
                Bc = next_pow2(k)
                chunks = np.zeros((Bc, C), np.int32)
                tbl = np.zeros((Bc, pool.max_blocks), np.int32)
                last_idx = np.zeros((Bc,), np.int32)
                for i, (req, slot, off) in enumerate(group):
                    piece = req.prompt[off:off + C]
                    chunks[i, :piece.shape[0]] = piece  # right-pad final
                    tbl[i] = pool.tables[slot]          # chunk; padded
                    last_idx[i] = min(prefill_end(req) - 1 - off, C - 1)
                    n_prefill_tokens += int(piece.shape[0])  # K/V -> trash
                donated = jax.tree.leaves(pool.cache)
                logits, pool.cache = prefill_fn(
                    self.small.params, jnp.asarray(chunks), jnp.asarray(tbl),
                    off0, jnp.asarray(last_idx), pool.cache)
                pool.count_inplace("prefill", donated)
                if dev_timer.enabled:
                    t_dev = tel.now
                    jax.block_until_ready((logits, pool.cache))
                    tel.phase_add("prefill", 0.0, tel.now - t_dev)
                n_prefill_dispatches += 1
                n_prefill_chunks += k
                seeded: List[Tuple[int, Request]] = []
                for i, entry in enumerate(group):
                    req, slot, off = entry
                    if off + C >= prefill_end(req):   # final chunk
                        prefilling.remove(entry)
                        if req.resume is not None:
                            # prompt blocks re-established: restore the
                            # decode tail + device rows; the row's seed
                            # logits are ignored (the snapshot carries
                            # the in-flight token instead)
                            apply_resume(slot, req)
                            if self.prefix_sharing:
                                pool.register_prefix(slot, req.prompt)
                            continue
                        state = finish_fn(state, slot, logits[i:i + 1],
                                          req.max_new, req.prompt_len)
                        if self.prefix_sharing:
                            # publish the fully-written prompt blocks so
                            # later same-prefix arrivals can map them
                            pool.register_prefix(slot, req.prompt)
                        req.t_prefill_done = tel.now
                        tel.event("prefill_done", rid=req.rid, slot=slot,
                                  chunks=math.ceil(
                                      max(req.prompt_len
                                          - req.shared_prefix_tokens, 1)
                                      / C),
                                  shared=req.shared_prefix_tokens)
                        seeded.append((slot, req))
                    else:
                        entry[2] = off + C
                if seeded:
                    if tr is not None:
                        seed_conf_trace(seeded)
                    sync_retire()        # max_new == 1: already finished

            def decoding_slots() -> List[int]:
                mid_prefill = {s for _, s, _ in prefilling}
                return [s for s in sched.active_slots
                        if s not in mid_prefill]

            # -- pressure machinery (oversubscribed paged pool only) ----
            def prefill_end(req: Request) -> int:
                """Last token (exclusive) the engine must PREFILL for this
                admission: the full prompt for a fresh request; for a
                resumed one only up to the first decode-written block —
                everything past that boundary is restored verbatim from
                the preemption snapshot, never recomputed (decode-written
                K/V is not bit-identical under a prefill recompute)."""
                return (req.prompt_len if req.resume is None
                        else req.resume["mb0"] * bs)

            def apply_resume(slot: int, req: Request) -> None:
                """Re-establish a preempted request in its new slot:
                restore the decode-written blocks from the host snapshot
                over the freshly mapped tail, then restore the device
                decode state verbatim. From here the request decodes as
                if it had never been evicted."""
                nonlocal state
                rs = req.resume
                pool.restore_block_span(slot, rs["mb0"] * bs,
                                        rs["ctx_len"], rs["blocks"])
                state = resume_fn(state, slot, rs["last_tok"], rs["pos"],
                                  rs["n_gen"], rs["conf_sum"],
                                  req.max_new, rs["tokens"])
                conf_prev[slot] = rs["conf_sum"]
                ngen_prev[slot] = rs["n_gen"]
                req.resume = None
                tel.event("resume", rid=req.rid, slot=slot,
                          n_gen=rs["n_gen"],
                          restored_blocks=len(rs["blocks"]))

            def preempt_slot(slot: int) -> None:
                """Evict the request in `slot` under block pressure with
                bit-exact resume state: snapshot its device rows and the
                blocks holding decode-written K/V, publish its prompt
                blocks in the prefix registry (resurrection makes the
                prompt recompute mostly a registry walk), release the
                slot, and requeue the request at its ORIGINAL arrival
                position (age-priority — repeated preemption cannot
                starve it behind fresh traffic)."""
                nonlocal state
                req = sched.running[slot]
                entry = next((e for e in prefilling if e[1] == slot), None)
                if entry is not None:
                    # mid-prefill victim: no decode state exists yet —
                    # keep the chunks already written via the registry
                    # and requeue as a plain re-admission
                    prefilling.remove(entry)
                    pool.register_prefix(slot, req.prompt[:entry[2]])
                    req.resume = None
                else:
                    lt, ps, ng, cs, toks = jax.device_get(
                        (state["last_tok"], state["pos"], state["n_gen"],
                         state["conf_sum"], state["tokens"]))
                    g = int(ng[slot])
                    ctx_len = req.prompt_len + g - 1
                    assert int(ps[slot]) == ctx_len, (slot, ps[slot],
                                                      ctx_len)
                    mb0 = req.prompt_len // bs
                    req.resume = {
                        "last_tok": int(lt[slot]), "pos": int(ps[slot]),
                        "n_gen": g, "conf_sum": float(cs[slot]),
                        "tokens": np.asarray(toks[slot]).copy(),
                        "ctx_len": ctx_len, "mb0": mb0,
                        "blocks": pool.save_block_span(slot, mb0 * bs,
                                                       ctx_len),
                    }
                    pool.register_prefix(slot, req.prompt)
                    state = dict(state)
                    state["active"] = state["active"].at[slot].set(False)
                sched.preempt(slot, tel.now)
                queue.requeue(req)
                tel.event("preempt", rid=req.rid, slot=slot,
                          n_preempted=req.n_preempted,
                          mid_prefill=entry is not None)

            def defer_oom(slot: int) -> None:
                """Defer the victim straight up the cascade ladder — the
                cascade's escape hatch under memory pressure: its blocks
                free immediately and the request still completes, on the
                next tier (`deferred_reason="oom"`)."""
                nonlocal state, n_oom_defers
                req = sched.running[slot]
                entry = next((e for e in prefilling if e[1] == slot), None)
                if entry is not None:
                    prefilling.remove(entry)
                    req.n_small_steps = 0
                    req.small_tokens = np.zeros(0, np.int32)
                else:
                    ng, cs, toks = jax.device_get(
                        (state["n_gen"], state["conf_sum"],
                         state["tokens"]))
                    n = int(ng[slot])
                    req.n_small_steps = n
                    req.small_tokens = np.asarray(toks[slot, :n]).copy()
                    req.confidence = float(cs[slot]) / max(n, 1)
                    state = dict(state)
                    state["active"] = state["active"].at[slot].set(False)
                req.deferred_reason = "oom"
                n_oom_defers += 1
                sched.retire(slot, tel.now, deferred=True, early=True)
                tel.event("defer_oom", rid=req.rid, slot=slot,
                          n_gen=req.n_small_steps)
                tel.m_requests.labels(outcome="defer_oom").inc()
                submit_large(req, 0)

            def finalize_shed(req: Request, terminal: str,
                              reason: str) -> None:
                """Terminal bookkeeping for a shed request: empty token
                vector, REJECTED/EXPIRED state, audit-log event, and the
                outcome counter — exactly once per request."""
                req.state = terminal
                req.tokens = np.zeros(0, np.int32)
                req.t_done = tel.now
                tel.event("shed", rid=req.rid, reason=reason,
                          outcome=terminal)
                tel.m_requests.labels(outcome=terminal).inc()

            def shed_slot(slot: int) -> None:
                """Drop an in-flight victim (shed pressure policy)."""
                nonlocal state
                req = sched.running[slot]
                entry = next((e for e in prefilling if e[1] == slot), None)
                if entry is not None:
                    prefilling.remove(entry)
                else:
                    state = dict(state)
                    state["active"] = state["active"].at[slot].set(False)
                sched.drop(slot, tel.now)
                finalize_shed(req, REJECTED, "shed_pressure")

            def relieve_pressure(exclude=()) -> bool:
                """Free physical blocks by evicting one deterministic
                victim (youngest admission) per the pressure policy.
                False when no victim exists — the caller must surface
                the pressure as a hard error."""
                nonlocal n_relief
                sel = policy.select(sched.running, exclude)
                if sel is None:
                    return False
                slot, action = sel
                if action == PREEMPT:
                    preempt_slot(slot)
                elif action == DEFER:
                    defer_oom(slot)
                else:
                    assert action == SHED, action
                    shed_slot(slot)
                n_relief += 1
                return True

            def with_relief(fn, needy=()):
                """Run `fn`, relieving `BlockPressure` by policy eviction
                and retrying (the pool's mapping calls are idempotent,
                so a retry resumes exactly where pressure struck).
                `needy` slots are exempt from victim selection — evicting
                the slot being mapped would livelock its own retry."""
                while True:
                    try:
                        return fn()
                    except BlockPressure:
                        if policy is None or not relieve_pressure(needy):
                            raise

            def admit() -> List[Tuple[int, Request]]:
                """Release arrivals and admit the FIFO head into free
                slots. Admission overload control runs BEFORE admitting:
                shed deadline-expired entries, then bound the queue
                (newest-first overflow); admitted requests are never
                expired — deadlines gate queueing only. Paged: one at a
                time, each admission reserving its blocks immediately so
                the capacity check for the next head sees the updated
                reservation; admitted requests queue for chunked
                prefill."""
                nonlocal n_shared_tokens
                if overload_on:
                    now = tel.now
                    queue.release(now)
                    for r in queue.expire(now):
                        finalize_shed(r, EXPIRED, "deadline")
                    for r in queue.shed_overflow():
                        finalize_shed(r, REJECTED, "queue_full")
                if not paged:
                    return sched.admit_ready(queue, tel.now)
                admitted = []
                relief0 = n_relief
                while True:
                    got = sched.admit_ready(
                        queue, tel.now, limit=1,
                        can_admit=lambda r: pool.can_reserve(
                            r.prompt_len + r.max_new - 1))
                    if not got:
                        break
                    slot, req = got[0]
                    pool.reserve(slot, req.prompt_len + req.max_new - 1)
                    rs = req.resume
                    end = prefill_end(req)
                    start = 0
                    if self.prefix_sharing or rs is not None:
                        # map already-resident (or cached) prefix blocks
                        # by refcount; prefill resumes at the first
                        # unshared token. A fully-shared prompt still
                        # recomputes its final token for the seed logits
                        # — run_prefill_chunk CoW-clones that block
                        # before the write. Preempted requests walk the
                        # same chain: their prompt blocks were registered
                        # at eviction, so the resume recompute is mostly
                        # (often entirely) a registry walk.
                        shared = pool.share_prefix(slot, req.prompt)
                        if rs is None:
                            start = min(shared, req.prompt_len - 1)
                            req.shared_prefix_tokens = start
                            n_shared_tokens += start
                        else:
                            start = min(shared, end)
                    L = req.prompt_len if rs is None else rs["ctx_len"]
                    with_relief(lambda s=slot, n=L: pool.ensure_mapped(s, n),
                                needy=(slot,))
                    if rs is not None and start >= end:
                        # every surviving prompt block came straight from
                        # the registry: restore the decode-written tail
                        # and resume now
                        apply_resume(slot, req)
                    else:
                        prefilling.append([req, slot, start])
                    admitted.append((slot, req))
                    if n_relief != relief0:
                        # pressure fired while admitting: stop — admitting
                        # more this iteration would thrash straight back
                        # into it
                        break
                if admitted:
                    tel.event("admit", rids=[r.rid for _, r in admitted],
                              slots=[s for s, _ in admitted],
                              shared=[r.shared_prefix_tokens
                                      for _, r in admitted])
                return admitted

            def decode_prep():
                """Paged: map every decoding slot's write span [pos,
                pos + steps_per_sync), CoW-clone any still-shared block
                in it so the in-flight write scatter stays row-disjoint,
                and slice the device page table to the bucketed active
                block prefix the masks can reach (the gather/kernel walk
                shrinks with it). Mapping can hit BlockPressure under
                oversubscription: relieve (the victim may itself be a
                decoding slot) and redo the prep against the survivors —
                ensure_mapped / ensure_writable are idempotent, so the
                retry resumes exactly where pressure struck. Returns the
                slots left to decode, their table and the span args
                `kv_pages` (the pages the paged kernel reads per layer:
                each decoding row's `pos // bs + 1`) and `table_pages`
                (rows x columns of the table, what a walk of all of it
                would visit) and, for a model with windowed layers,
                `win_pages` (the pages those layers' walks read, summed
                over rows and windowed layers)."""
                while True:
                    decoding = decoding_slots()
                    if not decoding:
                        return decoding, None, {}
                    try:
                        pos_host = np.asarray(state["pos"])
                        need = 1
                        covers = {}
                        for slot in decoding:
                            req = sched.running[slot]
                            total = req.prompt_len + req.max_new - 1
                            cover = min(int(pos_host[slot])
                                        + self.steps_per_sync, total)
                            pool.ensure_mapped(slot, cover)
                            pool.ensure_writable(slot, int(pos_host[slot]),
                                                 cover)
                            covers[slot] = cover
                            need = max(need, cover)
                        pool.check_write_disjoint(
                            (s, int(pos_host[s]), c)
                            for s, c in covers.items())
                        break
                    except BlockPressure:
                        if policy is None or not relieve_pressure():
                            raise
                tbl = pool.tables_device(pool.active_prefix_blocks(need))
                args = {"kv_pages": sum(int(pos_host[slot]) // bs + 1
                                        for slot in decoding),
                        "table_pages": tbl.shape[0] * tbl.shape[1]}
                if windows:
                    # pages the windowed layers' walks read: from the page
                    # holding pos - window + 1 through pos's, per layer
                    args["win_pages"] = sum(
                        int(p) // bs - max(int(p) - w + 1, 0) // bs + 1
                        for w in windows
                        for p in (pos_host[slot] for slot in decoding))
                return decoding, tbl, args

            def drain_ladder():
                """All M_S work is done: drain the ladder edge by edge.
                Backend e is flushed only once every backend upstream of
                it is empty — deferred traffic from edge e-1 is edge e's
                arrival traffic, so flushing earlier would cut partial
                batches that a sequential reference run would have
                batched together. Remote backends advertise
                drain_stall_timeout: when a replica dies mid-drain and
                nothing can make progress, abort with the pending count
                instead of spinning forever."""
                stalls = [getattr(b, "drain_stall_timeout", None)
                          for b in backends]
                stall_s = min((s for s in stalls if s is not None),
                              default=None)
                last_pending = total_pending()
                t_progress = time.perf_counter()
                while True:
                    for e, be in enumerate(backends):
                        if all(backends[u].n_pending == 0
                               for u in range(e)):
                            be.flush()
                    poll_large()
                    pending = total_pending()
                    if not pending:
                        return
                    if pending != last_pending:
                        last_pending = pending
                        t_progress = time.perf_counter()
                    elif (stall_s is not None
                          and time.perf_counter() - t_progress > stall_s):
                        names = [f"{getattr(b, 'name', '?')}:"
                                 f"{b.n_pending}" for b in backends]
                        raise RuntimeError(
                            f"M_L drain stalled: {pending} deferral(s) "
                            f"still pending ({', '.join(names)}) with no "
                            f"progress for {stall_s}s")
                    time.sleep(2e-3)

            try:
                while len(queue) or sched.n_active:
                    if profiler.enabled:
                        profiler.tick()
                    with span("iteration", n_active=sched.n_active,
                              ml_pending=ml.n_pending):
                        with span("schedule", phase="schedule"):
                            admitted = admit()
                        if prefilling:
                            with span("prefill", phase="prefill"):
                                run_prefill_chunk()
                        elif admitted and not paged:
                            with span("prefill", phase="prefill"):
                                admit_slot_groups(admitted)
                        peak_active = max(peak_active, sched.n_active)
                        decoding = decoding_slots()
                        if decoding:
                            # the decode phase holds its prep: the pos
                            # pull, block mapping and page table
                            with span("decode", phase="decode") as dec:
                                tables, pages = (), {}
                                if paged:
                                    with span("decode_prep"):
                                        decoding, tbl, pages = decode_prep()
                                    tables = (tbl,)
                                if decoding:
                                    step = n_steps // self.steps_per_sync
                                    with span("decode_dispatch", step=step,
                                              rows=len(decoding), **pages):
                                        donated = jax.tree.leaves(pool.cache)
                                        pool.cache, state = step_fn(
                                            self.small.params, pool.cache,
                                            state, *tables)
                                    if paged:
                                        pool.count_inplace("step", donated)
                                    if dev_timer.enabled:
                                        t_dev = tel.now
                                        jax.block_until_ready(state)
                                        tel.phase_add("decode", 0.0,
                                                      tel.now - t_dev)
                                    n_steps += self.steps_per_sync
                                    tel.event("step", step=step,
                                              slots=decoding,
                                              n=self.steps_per_sync,
                                              ml_pending=ml.n_pending)
                                    if tr is not None:
                                        record_conf_trace(decoding)
                                    sync_retire(step)
                            if decoding:
                                tel.m_decode_step.observe(
                                    dec.seconds / self.steps_per_sync)
                        elif not sched.n_active and len(queue):
                            nxt = queue.next_arrival
                            if nxt is not None:
                                with span("wait"):
                                    time.sleep(min(max(nxt - tel.now, 0.0),
                                                   1e-3) + 1e-5)
                        with span("ml_poll", phase="ml_wait"):
                            ml_depths.append(ml.n_pending)
                            poll_large()
                with span("drain", phase="drain"):
                    drain_ladder()
                makespan = tel.now
            finally:
                for be in backends:
                    be.close()
        finally:
            # a still-open jax.profiler window must be stopped even when
            # the run raises (leaking one poisons later profiled runs)
            profiler.close()
            compiles.close()
            tel.close()

        reqs = sorted(requests, key=lambda r: r.rid)
        if tr is not None:
            # request-lifecycle spans come from the recorded timestamps,
            # so their cost is paid once here, not in the serve loop
            emit_request_spans(tr, reqs)
        stats = tel.summary(reqs, makespan, self.cost_small,
                            self.cost_large)
        stats["backend"] = self.backend
        stats["cache_bytes"] = pool.footprint_bytes()
        stats["peak_active"] = peak_active
        stats["ml_backend"] = getattr(ml, "name",
                                      str(self.large_backend))
        stats["ml_batches"] = len(ml.batch_log)
        stats["ml_batch_occupancy"] = (
            float(np.mean([b["n_real"] / max(b["pad_to"], 1)
                           for b in ml.batch_log]))
            if ml.batch_log else float("nan"))
        stats["ml_batch_s"] = [{"batch_id": b["batch_id"],
                                "seconds": b["seconds"]}
                               for b in ml.batch_log if "seconds" in b]
        stats["compiles"] = compiles.counts
        if tfm.held_experts(self.small.cfg):
            stats["moe_hit_total"], stats["moe_assign_total"] = moe_totals
        stats["ml_queue_depth_peak"] = int(max(ml_depths, default=0))
        stats["ml_queue_depth_mean"] = (float(np.mean(ml_depths))
                                        if ml_depths else 0.0)
        # ladder accounting: reach[i] = fraction of traffic that paid
        # tier i (tier 0 always 1.0); compute_cost generalizes
        # cost_small + r * cost_large — bitwise identical for 2 tiers
        n_req = len(reqs)
        reach = [1.0] + [edge_deferrals[e] / n_req for e in range(n_edges)]
        stats["n_tiers"] = spec.n_tiers
        stats["tier_names"] = [t.name for t in spec.tiers]
        stats["tier_served"] = [sum(1 for r in reqs if r.tier == i)
                                for i in range(spec.n_tiers)]
        stats["edge_deferrals"] = list(edge_deferrals)
        stats["edge_tau"] = [edge_tau(e) for e in range(n_edges)]
        stats["edge_signal"] = [ed.signal.name for ed in spec.edges]
        stats["tier_reach"] = reach
        stats["compute_cost"] = ladder_compute_cost(reach, spec.costs)
        if n_edges > 1:
            stats["ml_backends"] = [getattr(b, "name", "?")
                                    for b in backends]
            stats["ml_batches_per_edge"] = [len(b.batch_log)
                                            for b in backends]
        if recal is not None:
            stats["recalibration"] = recal.summary()
        if paged:
            stats.update(block_size=self.block_size,
                         n_blocks=pool.n_blocks,
                         peak_blocks=pool.peak_mapped,
                         prefill_chunks=n_prefill_chunks,
                         prefill_dispatches=n_prefill_dispatches,
                         prefill_tokens=n_prefill_tokens,
                         prefix_sharing=self.prefix_sharing,
                         shared_tokens=n_shared_tokens,
                         shared_blocks=pool.shared_blocks_total,
                         cow_clones=pool.cow_clones,
                         pool_inplace=dict(pool.inplace_total),
                         paged_kernel=use_kernel)
            if pressure is not None:
                stats.update(oversubscribe=pressure.oversubscribe,
                             virtual_blocks=pool.virtual_blocks,
                             pressure_policy=pressure.policy,
                             pressure_reliefs=n_relief,
                             swap_blocks=pressure.swap_blocks,
                             swap_outs=pool.swap_outs,
                             swap_ins=pool.swap_ins,
                             swapped_blocks=pool.n_swapped_blocks)
        if own_obs:
            # engine-owned runtime: export the trace / metrics dump and
            # stop the endpoint now that the stats are final
            obs_rt.finish()
        # per-request final tokens are trimmed to each request's budget;
        # the matrix view pads the short rows back to the run width
        tokens = np.zeros((len(reqs), max_new), np.int32)
        for i, r in enumerate(reqs):
            tokens[i, :len(r.tokens)] = r.tokens
        result = ContinuousServeResult(
            requests=reqs,
            tokens=tokens,
            confidence=np.array([r.confidence for r in reqs]),
            deferred=np.array([r.deferred for r in reqs]),
            early_exited=np.array([r.early_exited for r in reqs]),
            deferral_ratio=float(np.mean([r.deferred for r in reqs])),
            saved_steps=sum(r.saved_steps for r in reqs),
            steps=n_steps,
            stats=stats,
        )
        return result

    # -- convenience: match the static engine's serve() signature ---------
    def serve(self, prompts: np.ndarray, prompt_len: int,
              max_new: int) -> ContinuousServeResult:
        """Uniform-batch convenience wrapper (static-engine signature);
        `prompt_len` must match the prompt matrix width."""
        if prompts.shape[1] != prompt_len:
            raise ValueError(f"prompt_len {prompt_len} != prompts width "
                             f"{prompts.shape[1]}")
        return self.run(make_requests(prompts, max_new), max_new)
