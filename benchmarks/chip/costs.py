"""Operations and bytes the served work needs, computed from shapes.

These are what the algorithm requires, not what a kernel happens to move:
no padding rows, no blocks past a row's valid prefix, no recomputation.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from model import Tier


def paged_attn_bytes(kv_tokens: int, rows: int, t: Tier,
                     itemsize: int = 2) -> int:
    """Bytes one paged GQA decode call must move for one layer: the K and
    V of every valid position of every active row (`kv_tokens` summed over
    rows), plus each active row's query and output."""
    kv = 2 * kv_tokens * t.n_kv_heads * t.head_dim * itemsize
    qo = 2 * rows * t.n_heads * t.head_dim * itemsize
    return kv + qo


def paged_attn_flops(kv_tokens: int, t: Tier) -> int:
    """FLOPs of one paged GQA decode call for one layer: q.k and p.v, two
    multiply-adds per head dimension, per head, per valid position."""
    return 4 * t.n_heads * t.head_dim * kv_tokens


def matmul_params_per_token(t: Tier) -> int:
    """Weights one token multiplies through in the trunk (all layers, no
    embedding lookup, no unembedding)."""
    d, H, KV, hd, f = (t.d_model, t.n_heads, t.n_kv_heads, t.head_dim,
                       t.d_ff)
    return t.n_layers * (d * H * hd + 2 * d * KV * hd + H * hd * d
                         + 3 * d * f)


def attn_flops_at(t: Tier, positions: Iterable[int]) -> int:
    """Attention FLOPs of the tokens at the given positions, each
    attending causally to itself and every earlier position."""
    return 4 * t.n_layers * t.n_heads * t.head_dim * sum(
        p + 1 for p in positions)


def decode_step_flops(t: Tier, positions: Iterable[int]) -> int:
    """FLOPs of one decode step over rows writing the given positions:
    each row's token through the trunk and the unembedding, and its
    attention to itself and every earlier position."""
    positions = list(positions)
    per_row = 2 * (matmul_params_per_token(t) + t.d_model * t.vocab_size)
    return per_row * len(positions) + attn_flops_at(t, positions)


def forward_flops_full_square(t: Tier, T: int) -> Tuple[int, int]:
    """(matmul, attention) FLOPs of one dense forward over T tokens that
    computes the full T x T score matrix and unembeds every position, as
    `repro.models.transformer.forward` does: what XLA's cost analysis
    counts, for the tests."""
    mm = 2 * (matmul_params_per_token(t) + t.d_model * t.vocab_size) * T
    att = 4 * t.n_layers * t.n_heads * t.head_dim * T * T
    return mm, att
