"""Drives the system under test: builds the cascade a configuration file
describes, warms every shape its traffic uses, and serves one schedule.

This is the only module that imports the program. It uses the program's
public serving surface (`ModelRunner`, `CascadeSpec`, `EngineConfig`,
`ContinuousCascadeEngine.run`, `Request`) and reads back the requests'
own timestamps and the engine's audit log.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import pathlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import model
import traffic as traffic_lib

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


class Compiles:
    """Counts backend compiles (persistent-cache loads included) through
    JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs


@dataclasses.dataclass
class Program:
    engine: Any
    runners: List[Any]
    archs: List[Any]            # each tier's architecture module
    tiers: List[Any]
    serving: dict
    taus: List[float]


def build(cfg: dict, archs: list, tiers: list, weights_seed: int
          ) -> Program:
    """Weights from `weights_seed`, one jitted call per tier through its
    architecture module (`model.load_tiers`), then the ladder."""
    from repro.serving import (CascadeSpec, CascadeTier,
                               ContinuousCascadeEngine, DeferralEdge,
                               EngineConfig, MLBackendConfig, ModelRunner,
                               PagedConfig)
    dtype = cfg["dtype"]
    runners = []
    for i, (arch, t) in enumerate(zip(archs, tiers)):
        weights = arch.make_weights(t, model.tier_key(weights_seed, i),
                                    DTYPES[dtype])
        runners.append(ModelRunner(*arch.program(t, weights, dtype)))
    sv = cfg["serving"]
    n = len(tiers)
    costs = [0.2 * (1.0 / 0.2) ** (i / (n - 1)) for i in range(n)]
    spec = CascadeSpec(
        tiers=[CascadeTier(t.name, runner=r, cost=c)
               for t, r, c in zip(tiers, runners, costs)],
        edges=[DeferralEdge(signal="mean_confidence", margin=sv["margin"],
                            min_tokens=sv["min_tokens"])
               for _ in range(n - 1)])
    config = EngineConfig(
        n_slots=sv["slots"], early_exit=sv["early_exit"], backend="paged",
        paged=PagedConfig(block_size=sv["block_size"],
                          n_blocks=sv["n_blocks"],
                          prefill_chunk=sv["prefill_chunk"],
                          paged_kernel=sv["paged_kernel"],
                          prefix_sharing=sv["prefix_sharing"]),
        ml=MLBackendConfig(kind=sv["large_backend"],
                           large_batch=sv["large_batch"],
                           max_wait=sv["max_wait_s"]))
    engine = ContinuousCascadeEngine(spec, config)
    jax.block_until_ready([r.params for r in runners])
    return Program(engine, runners, archs, tiers, sv, [])


def calibrate(prog: Program, cal_prompts: np.ndarray, max_new: int,
              hard_share: float) -> List[float]:
    taus = prog.engine.calibrate(cal_prompts, int(cal_prompts.shape[1]),
                                 max_new, hard_share)
    prog.taus = list(taus) if isinstance(taus, list) else [taus]
    return prog.taus


def _requests(specs, rid0: int = 0):
    from repro.serving import Request
    return [Request(rid=rid0 + i, prompt=np.asarray(s.prompt, np.int32),
                    max_new=int(s.max_new), arrival_time=float(s.arrival))
            for i, s in enumerate(specs)]


def _tokens(n: int, vocab: int, salt: int) -> np.ndarray:
    return (np.arange(n, dtype=np.int64) * 7919 + salt * 104729) % (
        vocab - 8) + 8


def warm_up(prog: Program, schedule: traffic_lib.Schedule, vocab: int
            ) -> Dict[str, float]:
    """Compile every program the schedule's window can dispatch, before
    the clock starts, and return the seconds of its two parts ("engine",
    "upper"):

    * the engine's prefill chunks at every power-of-two dispatch width up
      to next_pow2(slots), and its decode step at every power-of-two
      table prefix the schedule's positions can reach, with deferral off
      (taus at -inf) so that nothing reaches the upper tiers;
    * the slot-release scatter at every count of slots;
    * every upper tier's `generate` at each prompt length of the mix's
      grid, batch `large_batch`, width `max_new`, as the M_L backends
      call it.
    Every warm-up engine run has the schedule's longest request length,
    which the engine's compiled shapes are keyed on. The upper tiers'
    programs are warmed by running them: `ModelRunner.generate` compiles
    on its first call, and its public surface offers no other way."""
    eng, sv = prog.engine, prog.serving
    t0 = time.perf_counter()
    max_new, max_len = schedule.max_new, schedule.max_len
    bs, chunk, S = sv["block_size"], sv["prefill_chunk"], sv["slots"]
    saved = [e.tau for e in eng.spec.edges]
    for e in eng.spec.edges:
        e.tau = -math.inf
    Spec = traffic_lib.Spec
    try:
        # prefill widths: halving groups of prompts, the last one as long
        # as the longest request allows
        lens = traffic_lib.warmup_lengths(S, chunk, max_len - 2)
        specs = [Spec(_tokens(n, vocab, i), 2, 0.0, False)
                 for i, n in enumerate(lens)]
        specs[-1].max_new = max_len - lens[-1]
        eng.run(_requests(specs), max_new)
        # decode table prefixes: one probe decoding alone in each bucket,
        # beside a pin request that fixes the run's longest length and
        # never decodes (budget 1)
        max_blocks = math.ceil(max_len / bs)
        lo = math.ceil((schedule.mix["prompt"]["min"] + 1) / bs)
        b = 1
        while b < lo:
            b *= 2
        while True:
            probe = min(b * bs, max_len) - 2
            specs = [Spec(_tokens(max_len - 1, vocab, 1), 1, 0.0, False),
                     Spec(_tokens(probe, vocab, 2), 3, 0.0, False)]
            if probe + 3 >= max_len:
                specs = specs[1:]
                specs[0].prompt = _tokens(max_len - 3, vocab, 2)
            eng.run(_requests(specs), max_new)
            if b >= max_blocks:
                break
            b *= 2
    finally:
        for e, t in zip(eng.spec.edges, saved):
            e.tau = t
    # eager ops of the engine's host loop whose shapes vary with the batch:
    # the slot-release scatter (one per count of released slots) and the
    # row slice of a prefill dispatch's logits (one per row and width)
    active = jnp.zeros((S,), bool)
    for k in range(1, S + 1):
        active.at[jnp.asarray(list(range(k)))].set(False).block_until_ready()
    width = 1
    while True:
        logits = jnp.zeros((width, vocab), jnp.float32)
        for i in range(width):
            logits[i:i + 1].block_until_ready()
        if width >= S:
            break
        width *= 2
    t1 = time.perf_counter()
    grid = int(schedule.mix["prompt"].get("grid", 1))
    lengths = set(range(int(schedule.mix["prompt"]["min"]),
                        int(schedule.mix["prompt"]["max"]) + 1, grid))
    tail = schedule.traffic.get("tail_prompt_len")
    lengths = sorted(lengths | ({int(tail)} if tail else set()))
    for r in prog.runners[1:]:
        for n in lengths:
            r.generate(np.tile(_tokens(n, vocab, 3), (sv["large_batch"], 1)),
                       n, max_new)
    return {"engine": t1 - t0, "upper": time.perf_counter() - t1}


class TraceWindow:
    """Starts and stops `jax.profiler` from a helper thread at given
    offsets after `t0` (host perf_counter), so that the trace covers the
    middle of the measured window and not the ramp."""

    def __init__(self, out_dir: pathlib.Path, start: float, stop: float):
        self.out_dir, self.start, self.stop = out_dir, start, stop
        self.t_start = self.t_stop = float("nan")
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    def arm(self, t0: float):
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        daemon=True, name="trace-window")
        self._thread.start()

    def _run(self, t0: float):
        try:
            time.sleep(max(t0 + self.start - time.perf_counter(), 0.0))
            jax.profiler.start_trace(str(self.out_dir))
            # the window is from the start's return to the stop's call:
            # stopping writes the trace out, which takes seconds
            self.t_start = time.perf_counter() - t0
            time.sleep(max(t0 + self.stop - time.perf_counter(), 0.0))
            self.t_stop = time.perf_counter() - t0
            jax.profiler.stop_trace()
        except BaseException as e:                   # noqa: BLE001
            self.error = e

    def join(self):
        if self._thread is not None:
            self._thread.join()
        if self.error is not None:
            raise RuntimeError(f"trace window failed: {self.error!r}")


def serve(prog: Program, schedule: traffic_lib.Schedule,
          on_start: Callable[[float], None],
          audit_path: Optional[str] = None):
    """Serve the schedule through `ContinuousCascadeEngine.run`; returns
    (requests sorted by rid, stats, host perf_counter at the call)."""
    reqs = _requests(schedule.specs)
    t_call = time.perf_counter()
    on_start(t_call)
    res = prog.engine.run(reqs, schedule.max_new, audit_path=audit_path)
    return res.requests, res.stats, t_call


def free(prog: Program):
    """Delete the program's device state, so that the reference runs on a
    chip that holds nothing of it."""
    for r in prog.runners:
        for leaf in jax.tree.leaves(r.params):
            leaf.delete()
        r.params = None
    prog.engine = None
    prog.runners = []
    gc.collect()
