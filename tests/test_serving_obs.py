"""Observability-layer tests: metrics registry (Prometheus rendering,
pull-mode gauges, fixed-bucket histograms), the /metrics HTTP endpoint,
Chrome-trace export + schema/nesting validation (golden test against a
real engine run, cross-checked against the JSONL audit log), bounded
telemetry retention + streaming audit flush, device-time attribution,
and the parity contract: greedy outputs are bit-exact with the full
observability surface on vs off, on both KV backends."""
import json
import pathlib
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from repro.configs import get_config, reduced
from repro.data.synthetic import make_lm_stream
from repro.models import transformer as tfm
from repro.serving import (ContinuousCascadeEngine, ModelRunner,
                           make_requests)
from repro.serving.obs import (DeviceTimer, MetricsRegistry, MetricsServer,
                               ObsConfig, Observability, ProfilerWindow,
                               Tracer, validate_chrome_trace)
from repro.serving.request import Request
from repro.serving.telemetry import ServingTelemetry


@pytest.fixture(scope="module")
def runners():
    key = jax.random.PRNGKey(0)
    s_cfg = reduced(get_config("internlm2-1.8b"))
    l_cfg = s_cfg.replace(name="large", n_layers=3, d_ff=768)
    small = ModelRunner(s_cfg, tfm.init_params(s_cfg, key))
    large = ModelRunner(l_cfg, tfm.init_params(l_cfg,
                                               jax.random.fold_in(key, 1)))
    prompts = make_lm_stream(jax.random.fold_in(key, 2), 6, 10,
                             s_cfg.vocab_size)
    return small, large, prompts


@pytest.fixture(scope="module")
def tau_mixed(runners):
    """A threshold that defers roughly half the fixture prompts, so the
    traced run exercises both the keep and the defer/M_L path."""
    small, _, prompts = runners
    _, conf = small.generate(prompts, 10, 6)
    return float(np.median(conf))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_render():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labels=("outcome",))
    c.labels(outcome="ok").inc()
    c.labels(outcome="ok").inc(2)
    c.labels(outcome="err").inc()
    out = reg.render()
    assert "# HELP req_total requests" in out
    assert "# TYPE req_total counter" in out
    assert 'req_total{outcome="ok"} 3.0' in out
    assert 'req_total{outcome="err"} 1.0' in out
    assert out.endswith("\n")


def test_gauge_push_and_pull():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    g.set(4)
    assert g.value == 4.0
    state = {"n": 0}
    reg.gauge("live", "pull-mode", fn=lambda: state["n"])
    state["n"] = 7     # mutated after registration: read at render time
    assert "live 7.0" in reg.render()
    state["n"] = 9
    assert "live 9.0" in reg.render()


def test_histogram_cumulative_buckets_and_inf():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    out = reg.render()
    assert 'lat_bucket{le="0.1"} 1' in out
    assert 'lat_bucket{le="1.0"} 3' in out
    assert 'lat_bucket{le="+Inf"} 4' in out
    assert "lat_count 4" in out
    sum_line = next(l for l in out.splitlines()
                    if l.startswith("lat_sum"))
    assert float(sum_line.split()[1]) == pytest.approx(6.05)


def test_registry_get_or_create_and_collision():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total") is a        # get-or-create
    with pytest.raises(ValueError):
        reg.gauge("x_total")                  # type collision
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("k",))  # label collision
    with pytest.raises(ValueError):
        a.labels(wrong="v")                   # unknown label name


def test_label_value_escaping():
    reg = MetricsRegistry()
    c = reg.counter("esc_total", "", labels=("v",))
    c.labels(v='a"b\\c\nd').inc()
    assert r'esc_total{v="a\"b\\c\nd"} 1.0' in reg.render()


# ---------------------------------------------------------------------------
# /metrics endpoint
# ---------------------------------------------------------------------------

def test_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("scraped_total", "scrapes").inc(3)
    srv = MetricsServer(reg, port=0).start()
    try:
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            body = resp.read().decode()
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
        assert "scraped_total 3.0" in body
        # pull-mode gauges are live per scrape, not a snapshot
        reg.counter("scraped_total").inc()
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            assert "scraped_total 4.0" in resp.read().decode()
        bad = urllib.request.Request(
            f"http://{srv.host}:{srv.port}/nope")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(bad, timeout=5)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Tracer + validator
# ---------------------------------------------------------------------------

def test_tracer_export_schema(tmp_path):
    tr = Tracer()
    tr.name_process(1, "engine")
    tr.name_thread(1, 0, "loop")
    tr.complete("outer", "t", 0.0, 1.0, tid=0)
    tr.complete("inner", "t", 0.2, 0.3, tid=0)
    tr.instant("mark", "t", 0.5, tid=0)
    path = tmp_path / "t.json"
    tr.export(str(path))
    obj = json.loads(path.read_text())
    spans = validate_chrome_trace(obj)
    assert [s["name"] for s in spans] == ["outer", "inner"]
    meta = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    assert obj["displayTimeUnit"] == "ms"


def test_validator_rejects_partial_overlap():
    tr = Tracer()
    tr.complete("a", "t", 0.0, 1.0, tid=0)
    tr.complete("b", "t", 0.5, 1.0, tid=0)    # overlaps, not nested
    with pytest.raises(AssertionError, match="overlaps"):
        validate_chrome_trace(tr.export_obj())
    # same spans on DIFFERENT tracks are fine
    tr2 = Tracer()
    tr2.complete("a", "t", 0.0, 1.0, tid=0)
    tr2.complete("b", "t", 0.5, 1.0, tid=1)
    assert len(validate_chrome_trace(tr2.export_obj())) == 2


def test_validator_rejects_malformed_events():
    with pytest.raises(AssertionError):
        validate_chrome_trace({"notTraceEvents": []})
    with pytest.raises(AssertionError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "tid": 0, "ts": 1.0}]})


# ---------------------------------------------------------------------------
# Device timer / profiler window
# ---------------------------------------------------------------------------

def test_device_timer_split():
    import time
    x = jax.numpy.ones((64, 64))
    off = DeviceTimer(enabled=False)
    t0 = time.perf_counter()
    y = x @ x
    host, dev = off.split(t0, y)
    assert host >= 0 and dev == 0.0
    on = DeviceTimer(enabled=True)
    t0 = time.perf_counter()
    y = x @ x
    host, dev = on.split(t0, y)
    assert host >= 0 and dev >= 0.0         # blocked until ready


def test_profiler_window_state_machine(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    w = ProfilerWindow("/tmp/prof", n_iters=3)
    for _ in range(6):
        w.tick()
    w.close()
    w.close()                               # idempotent
    assert calls == [("start", "/tmp/prof"), ("stop",)]
    # disabled window never touches the profiler
    calls.clear()
    w2 = ProfilerWindow(None)
    w2.tick()
    w2.close()
    assert calls == []


# ---------------------------------------------------------------------------
# Telemetry retention / audit flush / summary keys
# ---------------------------------------------------------------------------

def test_event_retention_modes(tmp_path):
    tel = ServingTelemetry(max_events=3)
    for i in range(5):
        tel.event("step", i=i)
    assert tel.n_events == 5
    assert [e["i"] for e in tel.events] == [2, 3, 4]    # ring of last 3
    tel0 = ServingTelemetry(max_events=0)
    tel0.event("step")
    assert tel0.n_events == 1 and len(tel0.events) == 0
    # the audit log streams every event regardless of retention
    path = tmp_path / "audit.jsonl"
    tel_a = ServingTelemetry(str(path), max_events=0)
    for i in range(4):
        tel_a.event("step", i=i)
    tel_a.close()
    assert [json.loads(l)["i"] for l in path.read_text().splitlines()] \
        == [0, 1, 2, 3]


def test_audit_flush_every(tmp_path):
    path = tmp_path / "audit.jsonl"
    tel = ServingTelemetry(str(path), flush_every=2)
    tel.event("a")
    tel.event("b")          # hits the flush cadence
    tel.event("c")          # buffered
    flushed = path.read_text().splitlines()
    assert len(flushed) >= 2
    tel.close()
    assert len(path.read_text().splitlines()) == 3


def test_summary_queueing_and_phase_keys():
    def req(rid, arrival, admit, done):
        r = Request(rid=rid, prompt=np.zeros(4, np.int32), max_new=3,
                    arrival_time=arrival)
        r.t_admit, r.t_done = admit, done
        r.tokens = np.zeros(3, np.int32)
        r.n_small_steps = 3
        return r
    tel = ServingTelemetry()
    tel.phase_add("decode", 1.5)
    tel.phase_add("decode", 0.5, device_s=0.25)
    tel.phase_add("prefill", 0.75)
    reqs = [req(0, 0.0, 0.1, 1.0), req(1, 0.0, 0.3, 1.2)]
    s = tel.summary(reqs, makespan=2.0)
    assert s["queueing_p95_s"] == pytest.approx(0.29, abs=1e-6)
    assert s["phase_decode_s"] == pytest.approx(2.0)
    assert s["phase_prefill_s"] == pytest.approx(0.75)
    assert "device_timing" not in s          # mode was off
    tel.obs.device_timer.enabled = True
    s2 = tel.summary(reqs, makespan=2.0)
    assert s2["device_timing"] is True
    assert s2["phase_decode_device_s"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# Engine integration: golden trace, audit cross-check, parity, metrics
# ---------------------------------------------------------------------------

def _order_preserved(audit_ts, span_ts):
    """Every strict ordering in the audit log must be preserved by the
    trace span edges (ties in either are allowed — retirements within
    one sync share a timestamp)."""
    rids = list(audit_ts)
    for i, a in enumerate(rids):
        for b in rids[i + 1:]:
            if audit_ts[a] < audit_ts[b]:
                assert span_ts[a] <= span_ts[b] + 1e-6, (a, b)


@pytest.mark.parametrize("backend", ["slot", "paged"])
def test_trace_golden_and_obs_parity(runners, tau_mixed, tmp_path,
                                     backend):
    small, large, prompts = runners
    eng = ContinuousCascadeEngine(
        small, large, n_slots=3, tau=tau_mixed, min_tokens=2,
        early_exit=True, large_backend="thread", large_batch=2,
        large_max_wait=0.01, steps_per_sync=1, backend=backend,
        block_size=4, prefill_chunk=4)
    # every request arrives at t=0, so admission (and paged prefill
    # batching) follows from slot availability alone: the obs-on run,
    # whose device timing blocks the host, and the obs-off run below see
    # the same schedule, and parity does not hang on the wall clock
    arrivals = np.zeros(len(prompts))
    trace_path = tmp_path / f"trace_{backend}.json"
    metrics_path = tmp_path / f"metrics_{backend}.prom"
    audit_path = tmp_path / f"audit_{backend}.jsonl"
    cfg = ObsConfig(trace_path=str(trace_path),
                    metrics_path=str(metrics_path),
                    device_timing=True)
    res_on = eng.run(make_requests(prompts, 6, arrivals), 6,
                     audit_path=str(audit_path), obs=cfg)

    # -- golden: schema-valid, properly nested Chrome trace -------------
    obj = json.loads(trace_path.read_text())
    spans = validate_chrome_trace(obj)
    names = {s["name"] for s in spans}
    assert {"iteration", "decode", "prefill", "queued"} <= names
    by_req = {}
    for s in spans:
        if s["pid"] == 2:
            by_req.setdefault(s["tid"], {})[s["name"]] = s
    assert len(by_req) == len(prompts)       # one track per request
    for rid, sp in by_req.items():
        q, p, d = sp["queued"], sp["prefill"], sp["decode"]
        # lifecycle spans abut: queued -> prefill -> decode
        assert q["ts"] + q["dur"] == pytest.approx(p["ts"], abs=1.0)
        assert p["ts"] + p["dur"] == pytest.approx(d["ts"], abs=1.0)
        # per-token confidence record on the decode span
        conf = d["args"]["conf"]
        assert len(conf) == d["args"]["n_tokens"]
        req = res_on.requests[rid]
        assert len(conf) == req.n_small_steps
        assert np.mean(conf) == pytest.approx(req.confidence, abs=1e-4)
        if req.deferred:
            assert "ml_wait" in sp

    # -- audit-log cross-check: span edges preserve event order ---------
    audit = [json.loads(l) for l in audit_path.read_text().splitlines()]
    admit_ts = {r: e["t"] for e in audit if e["event"] == "admit"
                for r in e["rids"]}
    retire_ts = {e["rid"]: e["t"] for e in audit
                 if e["event"] == "retire"}
    assert set(admit_ts) == set(by_req)
    _order_preserved(admit_ts,
                     {r: sp["queued"]["ts"] + sp["queued"]["dur"]
                      for r, sp in by_req.items()})
    _order_preserved(retire_ts,
                     {r: sp["decode"]["ts"] + sp["decode"]["dur"]
                      for r, sp in by_req.items()})

    # -- metrics dump ---------------------------------------------------
    prom = metrics_path.read_text()
    for want in ("serving_tokens_total", "serving_requests_total",
                 "serving_decode_step_seconds_bucket",
                 "serving_phase_seconds_total",
                 "serving_ml_queue_depth", "serving_active_slots"):
        assert want in prom, want
    if backend == "paged":
        assert 'serving_pool_blocks{kind="total"}' in prom
    n_small = sum(len(r.tokens) for r in res_on.requests
                  if not r.deferred)
    assert f'serving_tokens_total{{model="small"}} {float(n_small)!r}' \
        in prom

    # -- device timing surfaced in the summary --------------------------
    assert res_on.stats["device_timing"] is True
    assert res_on.stats["phase_decode_device_s"] >= 0.0
    assert res_on.stats["queueing_p95_s"] >= 0.0

    # -- parity: bit-exact greedy outputs with observability off --------
    res_off = eng.run(make_requests(prompts, 6, arrivals), 6)
    assert np.array_equal(res_on.tokens, res_off.tokens)
    np.testing.assert_allclose(res_on.confidence, res_off.confidence,
                               rtol=0, atol=0)
    assert np.array_equal(res_on.deferred, res_off.deferred)


def test_caller_owned_observability_not_finished(runners, tau_mixed,
                                                 tmp_path):
    """A prebuilt Observability is fed but never exported by the engine:
    the caller decides when to finish (serve.py keeps /metrics open)."""
    small, large, prompts = runners
    eng = ContinuousCascadeEngine(small, large, n_slots=3, tau=tau_mixed,
                                  early_exit=False, steps_per_sync=1)
    trace_path = tmp_path / "t.json"
    obs = Observability(ObsConfig(trace_path=str(trace_path)))
    eng.run(make_requests(prompts, 4), 4, obs=obs)
    assert not trace_path.exists()          # engine did not finish it
    assert obs.registry.get("serving_tokens_total") is not None
    obs.finish()
    validate_chrome_trace(json.loads(trace_path.read_text()))


def test_bench_serving_obs_row_and_trace(runners, tmp_path, monkeypatch):
    """`bench_serving --trace-out` emits a valid Chrome trace and the
    gated continuous+obs row + queueing/phase keys in the bench record
    (acceptance criterion for the CI observability gate)."""
    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    import benchmarks.bench_serving as bs
    monkeypatch.setattr(bs, "save_result", lambda *a, **k: None)
    monkeypatch.setattr(bs, "emit_csv_row", lambda *a, **k: None)
    trace_path = tmp_path / "bench_trace.json"
    payload = bs.run(n_requests=4, max_new=4, slots=2,
                     ragged_min=8, ragged_max=8,
                     obs_cfg=ObsConfig(trace_path=str(trace_path)))
    validate_chrome_trace(json.loads(trace_path.read_text()))
    engines = [r["engine"] for r in payload["rows"]]
    assert "continuous+obs" in engines and "continuous" in engines
    assert payload["obs_overhead"] is not None
    rec = bs.bench_record(payload)
    row = next(r for r in rec["rows"] if r["engine"] == "continuous")
    assert row["queueing_p95_s"] is not None
    assert "decode" in row["phase_breakdown_s"]
