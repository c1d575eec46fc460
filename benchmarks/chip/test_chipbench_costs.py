"""The dense architecture's operation and byte counts against hand counts
at a tiny size, and against XLA's cost analysis of the program's own
forward pass."""
import pathlib

import jax
import jax.numpy as jnp
import pytest

import model

dense = model.load_arch(pathlib.Path(__file__).resolve().parent, "dense")

T = dense.Tier(name="t", n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
               head_dim=4, d_ff=16, vocab_size=32, rope_theta=1e4,
               norm_eps=1e-5)


def test_paged_attention_hand_counts():
    # 3 rows at positions 4, 9, 0 read 5 + 10 + 1 = 16 positions of K and
    # V: 16 * 2 kv heads * 4 dims * 2 bytes each, plus q and out
    assert dense.paged_attn_bytes(16, 3, T) == (2 * 16 * 2 * 4 * 2
                                                + 2 * 3 * 4 * 4 * 2)
    # q.k and p.v: 2 flops per dim per head per position, twice
    assert dense.paged_attn_flops(16, T) == 2 * 2 * 4 * 4 * 16
    # one identical call per layer
    assert dense.paged_attn_calls(T, [4, 9, 0], 3) == [
        (dense.paged_attn_bytes(16, 3, T), dense.paged_attn_flops(16, T))
    ] * 2


def test_decode_step_flops_hand_count():
    per_tok = 2 * (8 * 4 * 4 + 2 * 8 * 2 * 4 + 4 * 4 * 8 + 3 * 8 * 16)
    assert dense.matmul_params_per_token(T) == per_tok
    # two rows writing positions 3 and 0: each through the trunk and the
    # unembedding, two FLOPs a weight; attention over 4 and 1 positions,
    # per layer
    want = 2 * 2 * (per_tok + 8 * 32) + 4 * 2 * 4 * 4 * (4 + 1)
    assert dense.decode_step_flops(T, [3, 0]) == want
    assert dense.decode_step_flops(T, []) == 0


def test_forward_flops_match_xla_cost_analysis():
    """The program's full forward (repro.models.transformer.forward), as
    compiled by XLA for the CPU: its matmul and attention FLOPs are what
    dense.forward_flops_full_square counts."""
    from repro.models import transformer as tfm
    from repro.sharding import ParallelContext
    t = dense.Tier(name="t", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
                   rope_theta=1e4, norm_eps=1e-5)
    # unrolled layers: XLA's cost analysis counts a scan body once
    cfg, params = dense.program(t, dense.make_weights(
        t, model.tier_key(1, 0), jnp.float32), "float32")
    cfg = cfg.replace(scan_layers=False)
    tokens = jnp.zeros((1, 64), jnp.int32)
    fn = jax.jit(lambda p, x: tfm.forward(p, cfg, x, ParallelContext()))
    got = fn.lower(params, tokens).compile().cost_analysis()
    got = got[0] if isinstance(got, list) else got
    mm, att = dense.forward_flops_full_square(t, 64)
    assert got["flops"] == pytest.approx(mm + att, rel=0.05)
