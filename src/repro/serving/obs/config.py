"""Observability configuration + runtime facade for the serving stack.

``ObsConfig`` is the declarative knob set (CLI flags map 1:1 onto it);
``Observability`` owns the live objects — one `MetricsRegistry` (always,
bounded memory), plus the opt-in `Tracer`, `DeviceTimer`,
`ProfilerWindow`, and `MetricsServer` — and builds the always-on pieces
of a run: its span factory (`Spans`: profiler annotations, phase
attribution, the Chrome track when tracing) and its `CompileCounter`.

Everything beyond the registry is **off by default**: with a default
config the engine's instrumented paths see ``tracer is None``, a
disabled device timer, and no profiler — a branch test per site, no
retained spans, no forced device syncs. The parity contract (pinned by
tests/test_serving_obs.py) is that greedy outputs are bit-exact with
observability fully on vs fully off: instrumentation only ever *reads*
device state the engine already transfers (or blocks on it), never
changes what is computed.

Ownership: ``ContinuousCascadeEngine.run(..., obs=...)`` accepts either
an `ObsConfig` (the engine builds the runtime, runs, and calls
``finish()`` — the one-shot CLI/bench path) or a prebuilt
`Observability` (the caller keeps ownership and finishes it, e.g.
`serve.py` holding the /metrics endpoint open across the run).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.serving.obs.device_time import (CompileCounter, DeviceTimer,
                                          ProfilerWindow)
from repro.serving.obs.metrics import MetricsRegistry
from repro.serving.obs.trace import Spans, Tracer


@dataclasses.dataclass
class ObsConfig:
    """Declarative observability switches (all off/None by default)."""
    trace_path: Optional[str] = None     # Chrome-trace JSON out (Perfetto)
    metrics_path: Optional[str] = None   # Prometheus text dump at finish
    metrics_port: Optional[int] = None   # /metrics endpoint port (0 = any)
    device_timing: bool = False          # host/device split per dispatch
    profile_dir: Optional[str] = None    # jax.profiler capture directory
    profile_iters: int = 20              # engine iterations to capture
    audit_flush_every: int = 256         # JSONL flush cadence (events)
    max_events: Optional[int] = None     # telemetry retention (None = all,
                                         # 0 = none, N = ring of last N)

    @property
    def any_enabled(self) -> bool:
        return bool(self.trace_path or self.metrics_path
                    or self.metrics_port is not None or self.device_timing
                    or self.profile_dir)


def add_obs_args(ap) -> None:
    """Attach the shared observability CLI flags (serve.py and
    bench_serving.py expose the same set; they map 1:1 onto
    `ObsConfig` via :func:`obs_config_from_args`)."""
    g = ap.add_argument_group("observability")
    g.add_argument("--trace-out", default=None,
                   help="write a Chrome-trace-event JSON of the run "
                        "(load in https://ui.perfetto.dev)")
    g.add_argument("--metrics-out", default=None,
                   help="dump the final Prometheus text scrape to this "
                        "file")
    g.add_argument("--metrics-port", type=int, default=None,
                   help="serve a Prometheus /metrics endpoint on this "
                        "port during the run (0 = any free port)")
    g.add_argument("--device-timing", action="store_true",
                   help="bracket each dispatch with block_until_ready to "
                        "split host vs device wall time per phase "
                        "(serializes dispatch; outputs unchanged)")
    g.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the first "
                        "--profile-iters engine iterations here")
    g.add_argument("--profile-iters", type=int, default=20,
                   help="engine iterations inside the profiler window")
    g.add_argument("--audit-flush-every", type=int, default=256,
                   help="flush the JSONL audit log every N events")
    g.add_argument("--max-events", type=int, default=None,
                   help="in-memory telemetry event retention: unset = "
                        "keep all, 0 = keep none, N = ring of last N "
                        "(the audit log streams every event regardless)")


def obs_config_from_args(args) -> ObsConfig:
    """Build an `ObsConfig` from a parsed `add_obs_args` namespace."""
    return ObsConfig(trace_path=args.trace_out,
                     metrics_path=args.metrics_out,
                     metrics_port=args.metrics_port,
                     device_timing=args.device_timing,
                     profile_dir=args.profile_dir,
                     profile_iters=args.profile_iters,
                     audit_flush_every=args.audit_flush_every,
                     max_events=args.max_events)


class Observability:
    """Live observability objects for one (or more) engine runs."""

    def __init__(self, cfg: Optional[ObsConfig] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.cfg = cfg or ObsConfig()
        self.registry = registry or MetricsRegistry()
        self.tracer: Optional[Tracer] = (Tracer() if self.cfg.trace_path
                                         else None)
        self.device_timer = DeviceTimer(self.cfg.device_timing)
        self.profiler = ProfilerWindow(self.cfg.profile_dir,
                                       self.cfg.profile_iters)
        self.server = None

    def spans(self, clock, on_phase=None) -> Spans:
        """The engine's span factory (``engine.<name>``): profiler
        annotations always, phase attribution through `on_phase`, and
        the Chrome engine track when tracing is on. `clock` is the
        run-relative clock the tracer's spans use."""
        return Spans("engine", clock, self.tracer, on_phase)

    def count_compiles(self) -> CompileCounter:
        """Start counting backend compiles by function into the
        registry's ``serving_compiles_total{fn}``; the caller closes the
        counter when its run ends."""
        return CompileCounter(self.registry)

    def start_server(self):
        """Bind + start the /metrics endpoint when configured. Returns
        the `MetricsServer` (or None); safe to call once."""
        if self.cfg.metrics_port is not None and self.server is None:
            from repro.serving.obs.httpd import MetricsServer
            self.server = MetricsServer(self.registry,
                                        port=self.cfg.metrics_port).start()
        return self.server

    def finish(self) -> None:
        """Export the trace / metrics dump, stop the profiler and the
        endpoint. Idempotent; exporters only run when configured."""
        if self.tracer is not None and self.cfg.trace_path:
            self.tracer.export(self.cfg.trace_path)
        if self.cfg.metrics_path:
            self.registry.write(self.cfg.metrics_path)
        self.profiler.close()
        if self.server is not None:
            self.server.close()
            self.server = None
