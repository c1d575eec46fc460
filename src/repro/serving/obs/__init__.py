"""Serving observability: span tracing, metrics registry + Prometheus
export, and device-time attribution.

Three pillars (see docs/observability.md):

``trace``        Named host spans on the profiler's clock (``engine.*``,
                 ``ml.batch``: `jax.profiler.TraceAnnotation`, always
                 on), per-request lifecycle spans (queued -> prefill ->
                 decode -> ml_wait -> done, with the per-token eq.-8
                 confidence record on decode spans) and per-engine-
                 iteration phase spans, exported as Chrome trace-event
                 JSON loadable in Perfetto (``--trace-out``).
``metrics``      Counters / gauges / fixed-bucket histograms with zero
                 unbounded memory, rendered in Prometheus text format —
                 file dump (``--metrics-out``) or live ``/metrics``
                 endpoint (``httpd.MetricsServer``, ``--metrics-port``).
``device_time``  Opt-in host/device wall-time split per dispatch
                 (``--device-timing``), a bounded ``jax.profiler``
                 capture window (``--profile-dir``), and a per-run
                 compile counter by function.

``config.ObsConfig`` / ``config.Observability`` tie them together;
``ContinuousCascadeEngine.run(..., obs=...)`` accepts either. Everything
is off by default and the instrumented engine stays bit-exact and within
a few percent tokens/s of an uninstrumented run (gated in CI).
"""
from repro.serving.obs.config import (Observability, ObsConfig,
                                      add_obs_args, obs_config_from_args)
from repro.serving.obs.device_time import (CompileCounter, DeviceTimer,
                                          ProfilerWindow)
from repro.serving.obs.httpd import MetricsServer
from repro.serving.obs.metrics import (DEFAULT_BUCKETS, MetricFamily,
                                       MetricsRegistry)
from repro.serving.obs.trace import (PID_ENGINE, PID_REQUESTS, Spans,
                                     Tracer, emit_request_spans,
                                     validate_chrome_trace)

__all__ = [
    "DEFAULT_BUCKETS", "CompileCounter", "DeviceTimer", "MetricFamily", "MetricsRegistry",
    "MetricsServer", "ObsConfig", "Observability", "PID_ENGINE",
    "PID_REQUESTS", "ProfilerWindow", "Spans", "Tracer", "add_obs_args",
    "emit_request_spans", "obs_config_from_args", "validate_chrome_trace",
]
