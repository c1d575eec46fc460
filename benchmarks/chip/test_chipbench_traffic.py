"""The traffic generator: seeded, gridded, clipped, and the same work for
every seed."""
import collections
import json
import pathlib

import numpy as np
import pytest

import traffic

HERE = pathlib.Path(__file__).resolve().parent


def _schedule(seed, seconds=30.0):
    return traffic.build_schedule(HERE, "chat.rate.x2", seed, seconds,
                                  vocab=92544)


def test_same_seed_same_schedule():
    a, b = _schedule(2**31 + 17), _schedule(2**31 + 17)
    assert len(a.specs) == len(b.specs)
    for x, y in zip(a.specs, b.specs):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.arrival, x.measured) == \
            (y.max_new, y.arrival, y.measured)


def test_seeds_reorder_the_same_requests():
    a, b = _schedule(1), _schedule(2**33 + 5)
    reqs = lambda s, m: collections.Counter(
        (x.prompt.tobytes(), x.max_new) for x in s.specs if x.measured == m)
    assert reqs(a, True) == reqs(b, True)
    assert reqs(a, False) == reqs(b, False)
    assert len(reqs(a, True)) == sum(x.measured for x in a.specs)
    assert [x.prompt.shape[0] for x in a.specs] != \
        [x.prompt.shape[0] for x in b.specs]
    assert sorted(x.arrival for x in a.specs) != \
        sorted(x.arrival for x in b.specs)
    assert a.max_len == b.max_len
    assert sum(x.measured for x in a.specs) == sum(x.measured for x in b.specs)


def test_grid_and_clip():
    mix = json.loads((HERE / "mixes" / "chat.json").read_text())
    s = _schedule(3)
    for x in s.specs:
        n = x.prompt.shape[0]
        assert n % 64 == 0
        assert 128 <= n <= 2048
        assert mix["output"]["min"] <= x.max_new <= mix["output"]["max"]
        assert ((x.prompt >= 0) & (x.prompt < 92544)).all()
    lens = traffic.lognormal_lengths(mix["prompt"], 1000)
    assert lens.min() >= 128 and lens.max() == 2048
    assert 960 <= np.median(lens) <= 1088


def test_arrivals_fill_each_phase_at_the_rate():
    s = _schedule(4, seconds=20.0)
    t = json.loads((HERE / "traffic" / "chat.rate.x2.json").read_text())
    start, end = s.window
    assert (start, end) == (t["lead_s"], t["lead_s"] + 20.0)
    due = np.array([x.arrival for x in s.specs])
    meas = np.array([x.measured for x in s.specs])
    assert (due[meas] >= start).all() and (due[meas] < end).all()
    assert meas.sum() == int(np.ceil(t["rate_per_s"] * 20.0))
    tail = due[~meas & (due >= end)]
    assert len(tail) == int(np.ceil(t["rate_per_s"] * t["tail_s"]))
    assert all(x.prompt.shape[0] == t["tail_prompt_len"]
               for x in s.specs if x.arrival >= end)
    gaps = np.diff(np.sort(due[meas]))
    assert gaps.mean() == pytest.approx(1 / t["rate_per_s"], rel=0.1)


def test_phases_draw_from_the_mix():
    """The lead-in and the window carry the mix's own sizes; the tail too,
    but for the prompt length the traffic file fixes."""
    s = _schedule(5, seconds=40.0)
    start, end = s.window
    mix = json.loads((HERE / "mixes" / "chat.json").read_text())
    phases = {"lead": [x for x in s.specs if x.arrival < start],
              "window": [x for x in s.specs if x.measured],
              "tail": [x for x in s.specs if x.arrival >= end]}
    for name, xs in phases.items():
        n = len(xs)
        if name != "tail":
            want = sorted(traffic.lognormal_lengths(mix["prompt"], n))
            assert sorted(x.prompt.shape[0] for x in xs) == want, name
        want = sorted(traffic.lognormal_lengths(mix["output"], n))
        assert sorted(x.max_new for x in xs) == want, name


def test_tail_without_a_fixed_length_draws_from_the_mix(tmp_path):
    t = json.loads((HERE / "traffic" / "chat.rate.x2.json").read_text())
    t.pop("tail_prompt_len")
    for kind, name, body in (("traffic", "t", t), ("mixes", "chat", None)):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(
            json.dumps(body) if body else
            (HERE / "mixes" / "chat.json").read_text())
    s = traffic.build_schedule(tmp_path, "t", 5, 40.0, vocab=92544)
    mix = json.loads((HERE / "mixes" / "chat.json").read_text())
    tail = [x.prompt.shape[0] for x in s.specs if x.arrival >= s.window[1]]
    assert sorted(tail) == sorted(traffic.lognormal_lengths(mix["prompt"],
                                                            len(tail)))
def test_warmup_lengths_halve_through_every_width():
    lens = traffic.warmup_lengths(24, 256, 2302)
    assert len(lens) == 24
    counts = [sum(1 for n in lens if n > 256 * j) for j in range(10)]
    widths = {1 << (c - 1).bit_length() for c in counts if c}
    assert widths == {1, 2, 4, 8, 16, 32}
    assert max(lens) == 2302


def test_longest_request_is_fixed_by_the_mix():
    for seconds in (5.0, 12.0, 40.0, 51.0):
        assert _schedule(7, seconds).max_len == 2048 + 256
