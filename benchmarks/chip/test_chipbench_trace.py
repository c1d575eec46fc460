"""Trace reduction: busy time, per-program and per-kernel device time,
top operations and idle gaps, on hand-made events and on a trimmed trace
recorded on a v5e."""
import importlib.util
import json
import pathlib
import types

import numpy as np
import pytest

import model
import trace_reduce as tr
from trace_reduce import Event

HERE = pathlib.Path(__file__).resolve().parent
DEV = "/device:TPU:0"


def test_union_and_gaps_by_hand():
    ev = [Event(DEV, tr.OPS_LINE, "a", 0, 10),
          Event(DEV, tr.OPS_LINE, "b", 5, 10),       # overlaps a
          Event(DEV, tr.OPS_LINE, "a", 30, 5),
          Event(DEV, tr.MODULES_LINE, "jit_step_fn(1)", 0, 15),
          Event(DEV, tr.MODULES_LINE, "jit_step_fn(1)", 30, 5),
          Event("/host:CPU", "python", "dispatch", 14, 20)]
    red = tr.reduce(ev)
    assert red["busy_s"] == pytest.approx(20e-9)
    assert red["planes"][DEV]["gaps"] == [(15, 15)]
    assert tr.seconds_matching(red, "modules", "jit_step_fn") == \
        (pytest.approx(20e-9), 2)
    assert tr.seconds_matching(red, "ops", "a") == (pytest.approx(15e-9), 2)
    assert tr.top_ops(red) == [["jit_step_fn(1)/a", pytest.approx(15e-9)],
                               ["jit_step_fn(1)/b", pytest.approx(10e-9)]]
    assert tr.idle_gaps(red, ev) == [["dispatch", pytest.approx(15e-9)]]


def test_no_device_plane_reads_nothing():
    red = tr.reduce([Event("/host:CPU", "python", "x", 0, 5)])
    assert red["planes"] == {} and red["busy_s"] == 0.0
    assert tr.idle_gaps(red, []) == []


def test_recorded_v5e_trace():
    """124 ms of a backlog run on a v5e (two tier-0 decode steps, trimmed
    by hand from a chip trace): busy time against a 1-us timeline, and the
    decode program and paged kernel found by name."""
    ev = tr.read_events(HERE / "testdata" / "trace_v5e.json.gz")
    red = tr.reduce(ev)
    ops = [e for e in ev if e.plane == DEV and e.line == tr.OPS_LINE]
    grid = np.zeros(int(max(e.start_ns + e.dur_ns for e in ops) / 1e3) + 2,
                    bool)
    for e in ops:
        grid[int(e.start_ns / 1e3):int((e.start_ns + e.dur_ns) / 1e3)] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, abs=5e-5)
    sec, calls = tr.seconds_matching(red, "modules", "jit_step_fn")
    assert calls == 2 and 0.1 < sec < 0.125
    ksec, kcalls = tr.seconds_matching(red, "ops", "paged_flash_decode_gqa")
    assert kcalls == 48                      # 24 layers x 2 steps
    assert 0.5 * sec < ksec < sec
    top = tr.top_ops(red, 3)
    assert top[0][0].startswith("jit_step_fn/paged_flash_decode_gqa")
    assert top[0][1] == pytest.approx(ksec)
    assert all(not n.split("/")[-1].startswith("while") for n, _ in
               tr.top_ops(red))
    gaps = tr.idle_gaps(red, ev, 3)
    assert len(gaps) == 3 and gaps[0][1] >= gaps[1][1] > 0
    assert not any("queue.py" in n for n, _ in gaps)


def _reader(name):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# what each reader read on this trace and audit log while the harness
# counted tier 0 itself (costs.py): the dense module must give the same
READ_BEFORE = {"paged_attn_roofline.lat": 5.535180630786274,
               "step_mfu.lat": 0.503260840592426}


@pytest.mark.parametrize("name", ["paged_attn_roofline.lat",
                                  "step_mfu.lat"])
def test_shares_of_peak_on_the_recorded_trace(name):
    """The recorded trace's two decode steps, at 16 rows writing positions
    near 1100 (as the audit log would give them): each share of a peak
    lies in (0, 100] and reads as it did before the counts moved into the
    dense architecture module, and a run with no trace or no steps reads
    nothing."""
    cfg = json.loads((HERE / "configs" / "ilm2-1.8b.x2.json").read_text())
    archs, tiers = model.load_tiers(HERE, cfg, "ilm2-1.8b.x2.json")
    red = tr.reduce(tr.read_events(HERE / "testdata" / "trace_v5e.json.gz"))
    peaks = json.loads((HERE / "peaks.json").read_text())
    slots = list(range(16))
    audit = [{"t": 0.5, "event": "admit", "rids": slots, "slots": slots}]
    audit += [{"t": 0.6, "event": "prefill_done", "rid": s, "slot": s}
              for s in slots]
    audit += [{"t": 1.0 + i, "event": "step", "slots": slots, "n": 1}
              for i in range(2)]
    reqs = [types.SimpleNamespace(rid=s, prompt_len=1024 + 8 * s)
            for s in slots]
    run = types.SimpleNamespace(trace=red, audit=audit, reqs=reqs, cache={},
                                trace_window=(0.9, 3.0), archs=archs,
                                tiers=tiers,
                                peak=peaks["TPU v5 lite"], n_chips=1)
    read = _reader(name)
    assert 0.0 < read(run) <= 100.0
    assert read(run) == pytest.approx(READ_BEFORE[name], rel=1e-12)
    assert read(types.SimpleNamespace(**dict(run.__dict__, trace=None))) \
        is None
    empty = dict(run.__dict__, trace_window=(5.0, 6.0), cache={})
    assert read(types.SimpleNamespace(**empty)) is None
