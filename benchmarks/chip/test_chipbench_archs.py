"""The dense architecture module against what the harness computed before
architectures were modules (`testdata/dense_before.json`, recorded from
that code at the tiny cell's widths): the same weights, bit for bit, the
same reference readings, float32 for float32, and the same counts."""
import hashlib
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

import model
import tinybench

HERE = pathlib.Path(__file__).resolve().parent
BEFORE = json.loads((HERE / "testdata" / "dense_before.json").read_text())


@pytest.fixture(scope="module")
def dense():
    mod = model.load_arch(HERE, "dense")
    return mod, mod.tier(dict(tinybench.TIER, name="tiny-small"))


def _key():
    return model.tier_key(2**33 + 5, 1)


def _weights(mod, t):
    w = mod.make_weights(t, _key(), jnp.bfloat16)
    return {k: hashlib.sha256(np.asarray(v).view(np.uint16).tobytes())
            .hexdigest()[:16] for k, v in sorted(w.items())}


def _reference(mod, t):
    seqs = [model.Sequence_(np.arange(20, dtype=np.int32) * 7 % 500 + 3,
                            np.asarray([5, 99, 250, 7, 400], np.int32)),
            model.Sequence_(np.arange(37, dtype=np.int32) * 13 % 511,
                            np.asarray([1, 2, 3], np.int32))]
    res = mod.reference_readings(t, _key(), seqs, jnp.bfloat16,
                                 (None, "fp8"))
    return [{k: [[int(x) if k == "argmax" else float(x) for x in a]
                 for a in v] for k, v in r.items()} for r in res]


def _counts(mod, t):
    pos = BEFORE["counts"]["positions"]
    (b, f), = set(mod.paged_attn_calls(t, pos, len(pos)))
    assert len(mod.paged_attn_calls(t, pos, len(pos))) == t.n_layers
    return {"positions": pos,
            "decode_step_flops": mod.decode_step_flops(t, pos),
            "paged_attn_bytes": b, "paged_attn_flops": f}


@pytest.mark.parametrize("what,fn", [("weights", _weights),
                                     ("reference", _reference),
                                     ("counts", _counts)])
def test_dense_as_before(dense, what, fn):
    assert fn(*dense) == BEFORE[what]
