"""Device-time attribution: host/device wall-time split + profiler window.

JAX dispatch is asynchronous: the host returns from a jitted call as
soon as the work is enqueued, so host-side section timers conflate
"time spent driving the engine" with "time the accelerator was busy".
Two opt-in tools recover the split:

``DeviceTimer``
    When enabled, the engine brackets each dispatch with
    ``jax.block_until_ready`` on the dispatch result: the time up to
    the dispatch return is **host** (python + tracing + enqueue), the
    blocking remainder is **device** (XLA execution + transfer).
    Blocking serializes dispatch against execution, which can cost
    real overlap — that is why this is a *mode* (``--device-timing``)
    and not the default; outputs are bit-identical either way.

``ProfilerWindow``
    Captures a ``jax.profiler`` trace (XPlane, loadable in
    TensorBoard/Perfetto) for the first `n_iters` engine iterations of
    a run into ``profile_dir``. A bounded window rather than
    whole-run capture: profiler traces grow quickly and one window is
    what kernel-level analysis needs.

``CompileCounter``
    Counts backend compiles by function while a run is open, through
    JAX's monitoring events, so an operator sees which program
    recompiled (``serving_compiles_total{fn}`` on ``/metrics``).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class DeviceTimer:
    """Host/device split bracketing for jitted dispatches.

    Usage (engine hot path)::

        t0 = perf_counter()
        out = jitted_fn(...)
        host_s, device_s = timer.split(t0, out)

    Disabled (the default), ``split`` never blocks and reports the whole
    section as host time with device time 0 — callers record the pair
    unconditionally and the summary only advertises the split when the
    mode was on."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled

    def split(self, t_start: float, result: Any) -> tuple:
        """Returns (host_seconds, device_seconds) for a dispatch started
        at `t_start` (perf_counter) whose output tree is `result`."""
        t_disp = time.perf_counter()
        if not self.enabled:
            return t_disp - t_start, 0.0
        jax.block_until_ready(result)
        return t_disp - t_start, time.perf_counter() - t_disp


class ProfilerWindow:
    """Capture a jax.profiler trace for the first `n_iters` calls to
    ``tick()`` (one per engine iteration). Idempotent and crash-safe:
    ``close()`` stops a still-open capture."""

    def __init__(self, profile_dir: Optional[str], n_iters: int = 20):
        self.profile_dir = profile_dir
        self.n_iters = max(1, n_iters)
        self._i = 0
        self._running = False

    @property
    def enabled(self) -> bool:
        return self.profile_dir is not None

    def tick(self) -> None:
        if not self.enabled or self._i > self.n_iters:
            return
        if self._i == 0:
            jax.profiler.start_trace(self.profile_dir)
            self._running = True
        elif self._i == self.n_iters and self._running:
            jax.profiler.stop_trace()
            self._running = False
        self._i += 1

    def close(self) -> None:
        if self._running:
            jax.profiler.stop_trace()
            self._running = False


class CompileCounter:
    """Counts backend compiles (persistent-cache loads included) by
    function name from construction to ``close()``: a `jax.monitoring`
    duration listener on COMPILE_EVENT, which names the function
    (``jit(step_fn)`` counts as ``step_fn``). A compile on any thread of
    the process counts — the M_L worker compiles its own programs. With a
    registry the counts also go to ``serving_compiles_total{fn}``."""

    def __init__(self, registry=None):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}   # guarded_by: self._lock
        self._metric = (registry.counter(
            "serving_compiles_total",
            "backend compiles during engine runs, by function", ("fn",))
            if registry is not None else None)
        self._open = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, fun_name: str = "?",
                  **_) -> None:
        if event != COMPILE_EVENT:
            return
        fn = (fun_name[4:-1] if fun_name.startswith("jit(")
              and fun_name.endswith(")") else fun_name)
        with self._lock:
            self._counts[fn] = self._counts.get(fn, 0) + 1
        if self._metric is not None:
            self._metric.labels(fn=fn).inc()

    @property
    def counts(self) -> Dict[str, int]:
        """{function name: compiles} so far (a copy)."""
        with self._lock:
            return dict(self._counts)

    def close(self) -> None:
        """Stop counting (unregisters the listener); idempotent."""
        if self._open:
            jax.monitoring.unregister_event_duration_listener(self._on_event)
            self._open = False
