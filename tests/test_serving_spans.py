"""Named host spans on the profiler's clock, the upper tier's program
name, M_L batch seconds and the per-run compile counter: a tiny paged
engine that defers part of its traffic runs under `jax.profiler`, and
the trace is read back with `ProfileData`."""
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, reduced
from repro.data.synthetic import make_lm_stream
from repro.models import transformer as tfm
from repro.serving import (CascadeSpec, ContinuousCascadeEngine,
                           EngineConfig, MLBackendConfig, ModelRunner,
                           PagedConfig, make_requests)
from repro.serving.obs import (ObsConfig, Observability, Spans, Tracer,
                               validate_chrome_trace)

ENGINE_SPANS = ("iteration", "schedule", "prefill", "decode", "decode_prep",
                "decode_dispatch", "sync", "retire", "wait", "ml_poll",
                "drain")


@pytest.fixture(scope="module")
def runners():
    key = jax.random.PRNGKey(0)
    s_cfg = reduced(get_config("internlm2-1.8b"))
    l_cfg = s_cfg.replace(name="large", n_layers=3, d_ff=768)
    small = ModelRunner(s_cfg, tfm.init_params(s_cfg, key))
    large = ModelRunner(l_cfg, tfm.init_params(l_cfg,
                                               jax.random.fold_in(key, 1)))
    prompts = make_lm_stream(jax.random.fold_in(key, 2), 6, 8,
                             s_cfg.vocab_size)
    _, conf = small.generate(prompts, 8, 6)
    return small, large, prompts, float(np.median(conf))


def _engine(runners, tau, kind="thread"):
    small, large, _, _ = runners
    return ContinuousCascadeEngine(
        CascadeSpec.two_tier(small, large, tau=tau, min_tokens=2),
        EngineConfig(n_slots=3, backend="paged",
                     paged=PagedConfig(block_size=4, prefill_chunk=4),
                     ml=MLBackendConfig(kind=kind, large_batch=2,
                                        max_wait=0.01)))


def _host_spans(path):
    """{line id: [(name, start_ns, end_ns, args)]} of the `engine.*` and
    `ml.*` events on the host plane of a profile."""
    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("engine.", "ml.")):
                    out.setdefault((plane.name, i), []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


@pytest.fixture(scope="module")
def profiled(runners, tmp_path_factory):
    """Two warm-up runs with a late arrival (so the engine waits), then
    the same run under the profiler, with its audit log."""
    _, _, prompts, tau = runners
    eng = _engine(runners, tau)
    arrivals = np.array([0.0, 0.0, 0.0, 0.0, 0.0, 0.5])
    for _ in range(2):
        eng.run(make_requests(prompts, 6, arrivals), 6)
    d = tmp_path_factory.mktemp("profile")
    audit = d / "audit.jsonl"
    jax.profiler.start_trace(str(d))
    try:
        res = eng.run(make_requests(prompts, 6, arrivals), 6,
                      audit_path=str(audit))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(d / "plugins/profile/*/*.xplane.pb")))[-1]
    events = [json.loads(line) for line in audit.read_text().splitlines()]
    return res, _host_spans(path), events


def test_every_documented_span_is_in_the_profile(profiled):
    res, spans, _ = profiled
    assert res.deferred.any() and not res.deferred.all()
    names = {s[0] for line in spans.values() for s in line}
    assert {f"engine.{n}" for n in ENGINE_SPANS} <= names
    assert "ml.batch" in names


def test_engine_spans_nest_on_one_thread(profiled):
    _, spans, _ = profiled
    engine_lines = [line for line in spans.values()
                    if any(s[0] == "engine.iteration" for s in line)]
    assert len(engine_lines) == 1
    line = engine_lines[0]
    assert all(s[0].startswith("engine.") or s[0] == "ml.batch"
               for s in line)
    iters = [s for s in line if s[0] == "engine.iteration"]
    phases = [s for s in line if s[0] in ("engine.decode", "engine.prefill")]
    for s in line:
        if s[0] in ("engine.sync", "engine.retire"):
            assert _inside(s, phases), s
        if s[0] in ("engine.decode", "engine.prefill", "engine.schedule",
                    "engine.wait", "engine.ml_poll"):
            assert _inside(s, iters), s
        if s[0] in ("engine.decode_prep", "engine.decode_dispatch"):
            assert _inside(s, [p for p in phases
                               if p[0] == "engine.decode"]), s


def test_one_ml_batch_span_per_batch(profiled):
    res, spans, events = profiled
    batches = [s for line in spans.values() for s in line
               if s[0] == "ml.batch"]
    got = res.stats["ml_batch_s"]
    assert len(batches) == len(got) == res.stats["ml_batches"] > 0
    assert sorted(s[3]["batch_id"] for s in batches) == \
        sorted(b["batch_id"] for b in got)
    assert all(b["seconds"] > 0 for b in got)
    # the audit log joins each deferred request to its batch
    joined = {e["batch_id"] for e in events
              if e["event"] == "large_complete"}
    assert joined == {b["batch_id"] for b in got}


def test_dispatch_spans_and_audit_steps_share_the_index(profiled):
    res, spans, events = profiled
    disp = [s[3] for line in spans.values() for s in line
            if s[0] == "engine.decode_dispatch"]
    steps = [e for e in events if e["event"] == "step"]
    assert [d["step"] for d in disp] == [e["step"] for e in steps] \
        == list(range(res.steps))
    assert [d["rows"] for d in disp] == [len(e["slots"]) for e in steps]


def test_dispatch_spans_count_kernel_pages(profiled):
    """Each paged dispatch says how many pages the kernel reads per layer
    (each decoding row's `pos // block_size + 1`) against the page
    table's rows x columns."""
    _, spans, _ = profiled
    disp = [s[3] for line in spans.values() for s in line
            if s[0] == "engine.decode_dispatch"]
    assert disp
    for d in disp:
        assert d["rows"] <= d["kv_pages"] <= d["table_pages"]
        assert d["table_pages"] % 3 == 0          # n_slots rows
    # a row past its first block reads more than one page
    assert max(d["kv_pages"] - d["rows"] for d in disp) > 0


def test_upper_tier_program_is_named(runners):
    small, large, prompts, _ = runners
    large.generate(np.asarray(prompts), 8, 6)
    lowered = large._gen_fns[(8, 6)].lower(large.params,
                                           jnp.asarray(prompts))
    assert "@jit_ml_generate" in lowered.as_text()
    large.sample(np.asarray(prompts), 8, 3, seed=1)
    fn = large._gen_fns[(8, 3, 1.0, "sample")]
    lowered = fn.lower(large.params, jnp.asarray(prompts), jnp.uint32(1))
    assert "@jit_ml_sample" in lowered.as_text()


def test_compiles_counted_by_function(runners):
    """A cold engine names its programs; a warmed run compiles nothing; a
    deferral at a new prompt length compiles the upper tier's program
    mid-run; the listener is gone after the run."""
    small, _, prompts, _ = runners
    eng = _engine(runners, float("inf"), kind="sync")   # defer all
    prompts = np.asarray(prompts)
    cold = eng.run(make_requests(prompts[:3], 4), 4).stats["compiles"]
    assert {"step_fn", "prefill_chunk_fn", "ml_generate"} <= set(cold)
    warm = eng.run(make_requests(prompts[:3], 4), 4).stats["compiles"]
    assert warm == {}
    longer = np.concatenate([prompts[3], prompts[3, :4]])
    obs = Observability(ObsConfig())
    reqs = make_requests(prompts[:3], 4) + make_requests(longer[None], 4)
    for i, r in enumerate(reqs):
        r.rid = i
    res = eng.run(reqs, 4, obs=obs)
    assert res.stats["compiles"].get("ml_generate") == 1
    line = 'serving_compiles_total{fn="ml_generate"} 1.0'
    assert line in obs.registry.render()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
    assert obs.registry.render().count("serving_compiles_total{") == \
        len(res.stats["compiles"])


def test_spans_feed_phases_and_the_chrome_track(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name, **args):
            opened.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    phases = []
    t = iter([1.0, 1.5, 2.0, 4.0])
    tracer = Tracer()
    spans = Spans("engine", lambda: next(t), tracer,
                  lambda p, s: phases.append((p, s)))
    with spans("ml_poll", phase="ml_wait") as outer:
        with spans("sync", step=3) as inner:
            pass
    assert opened == [("engine.ml_poll", {}), ("engine.sync", {"step": 3})]
    assert (inner.seconds, outer.seconds) == (0.5, 3.0)
    assert phases == [("ml_wait", 3.0)]
    spans_out = validate_chrome_trace(tracer.export_obj())
    assert [(s["name"], s["cat"], s["ts"], s["dur"]) for s in spans_out] == \
        [("ml_poll", "engine", 1e6, 3e6), ("sync", "engine", 1.5e6, 0.5e6)]
    assert spans_out[1]["args"] == {"step": 3}


def test_dense_steps_carry_no_expert_counters(profiled):
    """A dense model's dispatches carry no window pages and its audit log
    no expert counters: nothing of the held-expert path runs."""
    res, spans, events = profiled
    disp = [s[3] for line in spans.values() for s in line
            if s[0] == "engine.decode_dispatch"]
    assert disp and all("win_pages" not in d for d in disp)
    assert not [e for e in events if e["event"] == "moe"]
    assert "moe_assign_total" not in res.stats


def test_windowed_moe_dispatch_pages_and_expert_counters(tmp_path):
    """Mellum (reduced: three 16-token-window layers and a full one, 2 of
    4 experts held): each dispatch's `win_pages` is the pages the
    windowed layers' walks read, from its rows' positions; the same
    step's audit event holds each layer's held experts hit and
    assignments, pulled with the step's sync, and their sum is the
    registry's counter."""
    key = jax.random.PRNGKey(3)
    cfg = reduced(get_config("mellum2-12b-a2.5b"))
    small = ModelRunner(cfg, tfm.init_params(cfg, key))
    large = ModelRunner(cfg.replace(name="l"), tfm.init_params(
        cfg, jax.random.fold_in(key, 1)))
    prompts = make_lm_stream(jax.random.fold_in(key, 2), 3, 30,
                             cfg.vocab_size)
    obs = Observability(ObsConfig())
    eng = ContinuousCascadeEngine(
        CascadeSpec.two_tier(small, large, tau=-1e9, min_tokens=2),
        EngineConfig(n_slots=3, backend="paged", early_exit=False,
                     paged=PagedConfig(block_size=4, prefill_chunk=8)))
    audit = tmp_path / "audit.jsonl"
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.run(make_requests(prompts, 8), 8, audit_path=str(audit),
                      obs=obs)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb")))
    spans = _host_spans(path[-1])
    events = [json.loads(line) for line in audit.read_text().splitlines()]
    disp = {s[3]["step"]: s[3] for line in spans.values() for s in line
            if s[0] == "engine.decode_dispatch"}
    steps = {e["step"]: e for e in events if e["event"] == "step"}
    moe = {e["step"]: e for e in events if e["event"] == "moe"}
    assert set(disp) == set(steps) == set(moe) and moe
    pos = {}
    for e in events:          # each slot's position at every step
        if e["event"] == "prefill_done":
            pos[e["slot"]] = 30
        elif e["event"] == "step":
            want = sum(p // 4 - max(p - 16 + 1, 0) // 4 + 1
                       for p in (pos[s] for s in e["slots"])) * 3
            assert disp[e["step"]]["win_pages"] == want
            for s in e["slots"]:
                pos[s] += 1
    for step, e in moe.items():
        rows = len(steps[step]["slots"])
        assert len(e["hit"]) == len(e["assign"]) == cfg.n_layers
        assert all(0 <= h <= min(2, a) for h, a in zip(e["hit"],
                                                       e["assign"]))
        assert all(a <= rows * cfg.moe.top_k for a in e["assign"])
    total = sum(sum(e["assign"]) for e in moe.values())
    assert res.stats["moe_assign_total"] == total > 0
    assert res.stats["moe_hit_total"] == sum(sum(e["hit"])
                                             for e in moe.values())
    snap = obs.registry.snapshot()
    assert snap["serving_moe_assignments_total"][()] == total
