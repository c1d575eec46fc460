"""The comparison that decides `correct`, on a tiny cell on the CPU: a
sound run passes, the float8 control fails, and so does a run whose
served tokens are altered where they are produced, on tier 0 and on the
top tier, and a run whose gate never or always defers. These drive the
whole of a run but the look for a chip."""
import math
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

import correct
import harness
import run
import tinybench


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinybench.make(tmp_path_factory.mktemp("tinybench"))


def _run(root, seed, **kw):
    return run.run_cell(pathlib.Path(root), "tiny.x2.rate", seed, 2.0,
                        trace=False, log=lambda *_: None, **kw)


def test_sound_run_passes_and_control_fails(root):
    res = _run(root, 2**31 + 3, control=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"latency_p90_s", "setup_s"} <= set(res["metrics"])
    ctrl = {k[len("control_"):]: v["value"] for k, v in checks.items()
            if k.startswith("control_")}
    assert any(ctrl[k] > checks[k]["limit"] for k in ctrl), checks


def test_tier0_token_altered_is_caught(root, monkeypatch):
    from repro.serving.engine import ContinuousCascadeEngine
    orig = ContinuousCascadeEngine._decode_body

    def altered(self, params, cache, state, pages, max_new,
                paged_kernel=None):
        cache, new = orig(self, params, cache, state, pages, max_new,
                          paged_kernel)
        act = state["active"]
        rows = jnp.arange(act.shape[0])
        col = jnp.clip(state["n_gen"], 0, max_new - 1)
        bad = (new["last_tok"] + 1) % self.small.cfg.vocab_size
        new = dict(new, last_tok=jnp.where(act, bad, new["last_tok"]),
                   tokens=new["tokens"].at[rows, col].set(
                       jnp.where(act, bad, new["tokens"][rows, col])))
        return cache, new

    monkeypatch.setattr(ContinuousCascadeEngine, "_decode_body", altered)
    res = _run(root, 11)
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_gap"]["value"] > \
        res["checks"]["served_gap"]["limit"]


def test_upper_tier_answer_altered_is_caught(root, monkeypatch):
    from repro.serving.engine import ModelRunner
    orig = ModelRunner.generate

    def altered(self, prompts, prompt_len, max_new):
        tokens, conf = orig(self, prompts, prompt_len, max_new)
        return (tokens + 1) % self.cfg.vocab_size, conf

    monkeypatch.setattr(ModelRunner, "generate", altered)
    res = _run(root, 12)
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_gap"]["value"] > \
        res["checks"]["served_gap"]["limit"]


@pytest.mark.parametrize("fault,tau", [("never", -math.inf),
                                       ("always", math.inf)])
def test_gate_that_never_or_always_defers_is_caught(root, monkeypatch,
                                                    fault, tau):
    """The program's first edge moved after calibration: it serves every
    request on tier 0, or sends every one up the ladder at its first
    chance. The served tokens are sound either way; the gate is not."""
    calibrate = harness.calibrate

    def moved(prog, *args):
        taus = calibrate(prog, *args)
        for e in prog.engine.spec.edges:
            e.tau = tau
        return taus

    monkeypatch.setattr(harness, "calibrate", moved)
    res = _run(root, 13)
    checks = res["checks"]
    assert not res["correct"], checks
    assert checks["served_gap"]["value"] <= checks["served_gap"]["limit"]
    assert checks["gate_miss"]["value"] > checks["gate_miss"]["limit"]


GATE = correct.Gate(tau=-1.0, margin=0.1, min_tokens=2, early_exit=True)


def _checked(deferred, early):
    return correct.Checked(0, int(deferred), np.zeros(1, np.int32),
                           np.zeros(1, np.int32), np.zeros(1, np.int32),
                           deferred, early, True)


@pytest.mark.parametrize("nent,deferred,early,want", [
    # kept: running means -0.5, -0.75, -0.8333; closest is the last, 0.1667
    # above tau
    ([-0.5, -1.0, -1.0], False, False, -1.0 + 0.8333333),
    # kept, but the final mean -1.05 is below tau: the reference defers
    ([-1.0, -1.0, -1.15], False, False, 0.05),
    # kept, but the mean at step 2 (-1.2) was below tau - margin: the
    # reference evicts there
    ([-1.0, -1.4, -0.1, -0.1], False, False, 0.1),
    # evicted at step 3 (mean -1.2 < -1.1): sound, 0.1 inside
    ([-1.0, -1.0, -1.6], True, True, -0.1),
    # evicted at step 3 with a mean (-1.0) above tau - margin
    ([-1.0, -1.0, -1.0], True, True, 0.1),
    # deferred at the end with a final mean of -0.9, above tau
    ([-0.9, -0.9, -0.9], True, False, 0.1),
])
def test_gate_miss_by_hand(nent, deferred, early, want):
    got = correct.gate_miss(np.asarray(nent), _checked(deferred, early), GATE)
    assert got == pytest.approx(want, abs=1e-6)
