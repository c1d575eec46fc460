"""End-to-end metric arithmetic and the per-layer readers that need no
trace."""
import math
import types

import pytest

import reading
import window


def req(rid, arrival, state="done", t_done=None, tokens=4, t_admit=None,
        deferred=False, t_retire=None, prompt_len=32, tier=0, n_small=4):
    return types.SimpleNamespace(
        rid=rid, arrival_time=arrival, state=state,
        t_done=math.nan if t_done is None else t_done,
        tokens=[7] * tokens if state == "done" else [],
        t_admit=math.nan if t_admit is None else t_admit,
        deferred=deferred, prompt_len=prompt_len, tier=tier,
        t_retire=math.nan if t_retire is None else t_retire,
        n_small_steps=n_small, max_new=tokens)


def test_unfinished_request_is_an_infinite_latency():
    reqs = [req(i, 1.0, t_done=2.0) for i in range(9)]
    reqs.append(req(9, 1.0, state="rejected"))
    lat = window.latencies(reqs, [True] * 10)
    assert lat.count(window.INF) == 1
    assert window.percentile(lat, 50) == pytest.approx(1.0)
    assert window.percentile(lat, 90) == pytest.approx(1.0)
    assert window.percentile(lat, 95) == window.INF


def test_percentile_nearest_rank():
    v = list(range(1, 11))
    assert window.percentile(v, 50) == 5
    assert window.percentile(v, 90) == 9
    assert window.percentile(v, 100) == 10
    with pytest.raises(ValueError):
        window.percentile([1.0, math.nan], 50)


def test_unmeasured_requests_are_left_out():
    reqs = [req(0, 0.0, t_done=100.0), req(1, 1.0, t_done=1.5)]
    assert window.latencies(reqs, [False, True]) == [0.5]


def test_unfinished_requests_are_attempted_and_failed():
    reqs = [req(0, 0.0, t_done=1.0), req(1, 0.0, state="rejected"),
            req(2, 0.0, state="running"), req(3, 0.0, t_done=1.0)]
    c = window.outcome_counts(reqs, [True, True, True, False])
    assert c == {"attempted": 3, "failed": 2}


def test_replay_positions_from_the_audit_log():
    events = [
        {"t": 0.0, "event": "admit", "rids": [0, 1], "slots": [0, 1]},
        {"t": 0.1, "event": "prefill_done", "rid": 0, "slot": 0},
        {"t": 0.2, "event": "step", "slots": [0], "n": 1},
        {"t": 0.3, "event": "prefill_done", "rid": 1, "slot": 1},
        {"t": 0.4, "event": "step", "slots": [0, 1], "n": 1},
        {"t": 0.5, "event": "retire", "rid": 0, "slot": 0},
        {"t": 0.6, "event": "admit", "rids": [2], "slots": [0]},
        {"t": 0.7, "event": "prefill_done", "rid": 2, "slot": 0},
        {"t": 0.8, "event": "step", "slots": [0, 1], "n": 1},
    ]
    steps = reading.replay_steps(events, {0: 10, 1: 20, 2: 5})
    assert [s["positions"] for s in steps] == [[10], [11, 20], [5, 21]]
    run = types.SimpleNamespace(audit=events, reqs=[
        req(0, 0, prompt_len=10), req(1, 0, prompt_len=20),
        req(2, 0, prompt_len=5)], cache={})
    assert [s["t"] for s in reading.steps_in(run, 0.3, 0.8)] == [0.4]


def test_ml_batches_deduplicated_per_tier():
    ev = [{"t": 1.0, "event": "large_complete", "tier": 1, "batch_id": 0,
           "n_real": 2, "pad_to": 8},
          {"t": 1.0, "event": "large_complete", "tier": 1, "batch_id": 0,
           "n_real": 2, "pad_to": 8},
          {"t": 1.5, "event": "large_complete", "tier": 2, "batch_id": 0,
           "n_real": 1, "pad_to": 8},
          {"t": 9.0, "event": "large_complete", "tier": 1, "batch_id": 1,
           "n_real": 8, "pad_to": 8}]
    run = types.SimpleNamespace(audit=ev)
    got = reading.ml_batches(run, 0.0, 5.0)
    assert sorted(got) == [(1, 0), (2, 0)]
