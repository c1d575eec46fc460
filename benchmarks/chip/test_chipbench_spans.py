"""The readers of the program's own spans and counters: host_idle_ms.lat
on hand-made trace events, ml_batch_s.lat on hand-made stats and audit
events."""
import importlib.util
import pathlib
import types

import pytest

import trace_reduce as tr
from trace_reduce import Event

HERE = pathlib.Path(__file__).resolve().parent
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _reader(name):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _device(*busy):
    """Two decode steps' programs and their ops at the given [start, end)
    intervals (ns)."""
    ev = [Event(DEV, tr.MODULES_LINE, "jit_step_fn(7)", s, e - s)
          for s, e in busy[:2]]
    ev += [Event(DEV, tr.OPS_LINE, f"fusion.{i}", s, e - s)
           for i, (s, e) in enumerate(busy)]
    return ev


def _run(events):
    return types.SimpleNamespace(trace=tr.reduce(events), events=events)


def _span(name, start, end):
    return Event(HOST, "python", name, start, end - start)


# device busy [0, 100), [300, 400), [1000, 1100): gaps (100, 200) and
# (400, 600)
BUSY = ((0, 100), (300, 400), (1000, 1100))
ITER = [_span("engine.iteration", 0, 2000)]


@pytest.mark.parametrize("spans,idle_ns", [
    # a gap inside engine.sync counts, as far as the span covers it
    ([_span("engine.sync", 100, 250)], 150),
    ([_span("engine.sync", 50, 350)], 200),
    # inside engine.wait, inside the iteration alone, or inside no span:
    # nothing
    ([_span("engine.wait", 400, 1000)], 0),
    ([], 0),
    # two nested spans over one gap count once
    ([_span("engine.decode", 380, 1000), _span("engine.retire", 450, 900)],
     600),
    # two gaps under two spans, one span reaching over a busy interval
    ([_span("engine.prefill", 150, 450), _span("engine.ml_poll", 900, 950)],
     150 + 50 + 50),
])
def test_host_idle_counts_the_gaps_under_engine_spans(spans, idle_ns):
    read = _reader("host_idle_ms.lat")
    run = _run(_device(*BUSY) + ITER + spans)
    assert read(run) == pytest.approx(idle_ns * 1e-6 / 2)


def test_host_idle_reads_nothing_without_spans_or_steps():
    read = _reader("host_idle_ms.lat")
    # a program without engine spans (the parent of this metric)
    assert read(_run(_device(*BUSY))) is None
    # no decode step in the trace
    no_steps = [e for e in _device(*BUSY) if e.line == tr.OPS_LINE]
    assert read(_run(no_steps + [_span("engine.sync", 100, 250)])) is None
    assert read(types.SimpleNamespace(trace=None, events=None)) is None


def _ml_run(measured, batches, stats_key=True):
    reqs = [types.SimpleNamespace(rid=i) for i in range(len(measured))]
    audit = [{"t": 1.0, "event": "large_complete", "rid": rid, "tier": 1,
              "batch_id": bid} for bid, rids in batches.items()
             for rid in rids]
    stats = {"ml_batch_s": [{"batch_id": bid, "seconds": 1.0 + bid}
                            for bid in batches]} if stats_key else {}
    return types.SimpleNamespace(reqs=reqs, measured=measured, audit=audit,
                                 stats=stats)


def test_ml_batch_keeps_batches_with_a_due_request():
    read = _reader("ml_batch_s.lat")
    # requests 0 and 3 are due; batch 0 holds 0 and 1, batch 1 holds 2
    # only, batch 2 holds 3, batch 3 holds 4
    measured = [True, False, False, True, False]
    batches = {0: [0, 1], 1: [2], 2: [3], 3: [4]}
    assert read(_ml_run(measured, batches)) == pytest.approx(2.0)
    assert read(_ml_run([False, False, False, True, False], batches)) == \
        pytest.approx(3.0)


def test_ml_batch_reads_nothing_without_due_batches():
    read = _reader("ml_batch_s.lat")
    assert read(_ml_run([False, True], {0: [0]})) is None
    assert read(_ml_run([True], {})) is None
    # a program that reports no batch seconds (the parent of this metric)
    assert read(_ml_run([True], {0: [0]}, stats_key=False)) is None
    no_audit = _ml_run([True], {0: [0]})
    no_audit.audit = None
    assert read(no_audit) is None
