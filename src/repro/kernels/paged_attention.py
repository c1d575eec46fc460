"""Paged flash-decode Pallas kernels (TPU target) + in-kernel paged write.

Decode hot spot of the paged serving backend: one-token attention computed
DIRECTLY against the block-paged KV cache. The XLA fallback in
`models/attention.py` materializes a dense `[B, M*block_size, ...]` view of
every row's pages (`gather_blocks`) before attending — O(B * view) HBM
traffic per decoded token regardless of how much of the view is valid.
These kernels never build that view:

  * the kv grid dimension walks each row's page table via scalar prefetch
    (`PrefetchScalarGridSpec`): step (b, j) DMAs exactly ONE
    `[block_size, ...]` physical block, `tables[b, j]`, into VMEM;
  * unmapped pages (table entry 0 = the shared trash block) and pages
    entirely beyond the row's `idx <= pos` prefix are early-masked — the
    online-softmax state is simply not updated, so trash contents can
    never contribute (rows whose table is all zeros produce exact zeros);
  * fp32 (m, l, acc) online-softmax scratch lives in VMEM across the kv
    walk; the final kv step normalizes and writes the output tile.

Two variants share the dataflow:
  * GQA  — paged K/V `[n_blocks+1, block_size, KV, hd]`; kv head = q head
    // group, computed in-kernel on the `[KV, group]` score layout.
  * MLA  — weight-absorbed decode against the COMPRESSED cache
    (`ckv` `[*, kv_lora]` + shared rope key `[*, rope_dim]`): the kernel
    applies the kv rms-norm per block in fp32 and returns the latent
    context `[B, 1, H, kv_lora]`; the caller applies W_uv / W_o.

`paged_write_token` is the companion single-token scatter: grid (B,), the
output BlockSpec selects the physical block holding each row's `pos`
through the page table, and the kernel rewrites only `pos % block_size`
(input/output aliased, so no dense copy of the leaf). Rows whose target
block is unmapped write into the trash block — same contract as the XLA
`_paged_write` they replace. Mapped blocks are pairwise disjoint across
rows (pool invariant), so block revisits only ever hit the trash block.

Oracle: the dense-gather paths in `models/attention.py` (parity pinned by
tests/test_paged_kernel.py). Wrappers with interpret-mode defaults live in
kernels/ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


# ---------------------------------------------------------------------------
# GQA paged flash-decode
# ---------------------------------------------------------------------------

def _gqa_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref, *, n_mb: int, bs: int, group: int,
                scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page = tbl_ref[b, j]
    pos = pos_ref[b]
    # early-mask: skip unmapped/trash pages and pages fully beyond the
    # row's valid prefix — their DMA'd block never touches the softmax
    valid_block = (page > 0) & (j * bs <= pos)

    @pl.when(valid_block)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)              # [H, hd]
        k = k_ref[0].astype(jnp.float32)                 # [bs, KV, hd]
        v = v_ref[0].astype(jnp.float32)
        KV = k.shape[1]
        qg = q.reshape(KV, group, q.shape[-1])
        s = jnp.einsum("kgh,skh->kgs", qg, k,
                       preferred_element_type=jnp.float32) * scale
        idx = j * bs + jax.lax.broadcasted_iota(jnp.int32,
                                                (KV, group, bs), 2)
        mask = idx <= pos                                # per-row validity
        s = jnp.where(mask, s, NEG)
        bm = s.max(axis=-1)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, bm)
        alpha = jnp.exp(m_old - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
            "kgs,skh->kgh", p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_mb - 1)
    def _final():
        H, hd = q_ref.shape[2], q_ref.shape[3]
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[..., None]
                       ).reshape(H, hd).astype(o_ref.dtype)


def paged_flash_decode_gqa(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, tables: jnp.ndarray,
                           positions: jnp.ndarray, *,
                           scale: float | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q [B,1,H,hd]; k/v_pages [n_blocks+1, block_size, KV, hd];
    tables [B, M] int32 (0 = unmapped); positions [B] int32. Returns
    [B,1,H,hd] — masked-softmax attention over each row's valid prefix,
    identical (up to fp32 online-softmax rounding) to the dense-gather
    path. Fully-unmapped rows return exact zeros."""
    B, T, H, hd = q.shape
    assert T == 1
    bs, KV = k_pages.shape[1], k_pages.shape[2]
    M = tables.shape[1]
    group = H // KV
    scale = scale or 1.0 / np.sqrt(hd)
    kernel = functools.partial(_gqa_kernel, n_mb=M, bs=bs, group=group,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, 1, H, hd), lambda b, j, t, p: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, j, t, p: (t[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, j, t, p: (t[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, hd), lambda b, j, t, p: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((KV, group), jnp.float32),
                        pltpu.VMEM((KV, group), jnp.float32),
                        pltpu.VMEM((KV, group, hd), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), positions.astype(jnp.int32), q,
      k_pages, v_pages)


# ---------------------------------------------------------------------------
# MLA paged flash-decode (weight-absorbed, compressed cache)
# ---------------------------------------------------------------------------

def _mla_kernel(tbl_ref, pos_ref, qa_ref, qr_ref, ckv_ref, kr_ref, w_ref,
                o_ref, m_ref, l_ref, acc_ref, *, n_mb: int, bs: int,
                scale: float, eps: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page = tbl_ref[b, j]
    pos = pos_ref[b]
    valid_block = (page > 0) & (j * bs <= pos)

    @pl.when(valid_block)
    def _():
        ckv = ckv_ref[0].astype(jnp.float32)             # [bs, r]
        var = jnp.mean(ckv * ckv, axis=-1, keepdims=True)
        ckv_n = ckv * jax.lax.rsqrt(var + eps) * (
            1.0 + w_ref[...].astype(jnp.float32))        # kv rms-norm
        kr = kr_ref[0].astype(jnp.float32)               # [bs, dr]
        qa = qa_ref[0, 0].astype(jnp.float32)            # [H, r] (absorbed)
        qr = qr_ref[0, 0].astype(jnp.float32)            # [H, dr]
        s = (jnp.dot(qa, ckv_n.T, preferred_element_type=jnp.float32)
             + jnp.dot(qr, kr.T, preferred_element_type=jnp.float32)) * scale
        H = s.shape[0]
        idx = j * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
        mask = idx <= pos
        s = jnp.where(mask, s, NEG)
        bm = s.max(axis=-1)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, bm)
        alpha = jnp.exp(m_old - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, ckv_n, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_mb - 1)
    def _final():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def paged_flash_decode_mla(q_abs: jnp.ndarray, q_rope: jnp.ndarray,
                           ckv_pages: jnp.ndarray, kr_pages: jnp.ndarray,
                           kv_norm: jnp.ndarray, tables: jnp.ndarray,
                           positions: jnp.ndarray, *, scale: float,
                           eps: float = 1e-6,
                           interpret: bool = False) -> jnp.ndarray:
    """Weight-absorbed MLA decode against the paged compressed cache.

    q_abs [B,1,H,r] (q_nope absorbed through W_uk); q_rope [B,1,H,dr];
    ckv_pages [n_blocks+1, bs, r]; kr_pages [n_blocks+1, bs, dr];
    kv_norm [r]. Returns the latent context [B,1,H,r] in fp32 — the
    caller applies W_uv and W_o (scores AND values stay O(kv_lora))."""
    B, T, H, r = q_abs.shape
    assert T == 1
    dr = q_rope.shape[-1]
    bs = ckv_pages.shape[1]
    M = tables.shape[1]
    kernel = functools.partial(_mla_kernel, n_mb=M, bs=bs, scale=scale,
                               eps=eps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, 1, H, r), lambda b, j, t, p: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, H, dr), lambda b, j, t, p: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, r), lambda b, j, t, p: (t[b, j], 0, 0)),
            pl.BlockSpec((1, bs, dr), lambda b, j, t, p: (t[b, j], 0, 0)),
            pl.BlockSpec((r,), lambda b, j, t, p: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, r), lambda b, j, t, p: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H,), jnp.float32),
                        pltpu.VMEM((H,), jnp.float32),
                        pltpu.VMEM((H, r), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, r), jnp.float32),
        interpret=interpret,
    )(tables.astype(jnp.int32), positions.astype(jnp.int32),
      q_abs, q_rope, ckv_pages, kr_pages, kv_norm)


# ---------------------------------------------------------------------------
# In-kernel single-token paged write
# ---------------------------------------------------------------------------

def _write_kernel(phys_ref, pos_ref, val_ref, leaf_ref, out_ref, *, bs: int):
    # masked whole-block write: a store at a dynamic row of a packed bf16
    # tile cannot be proven tile-aligned by the TPU compiler, a select
    # over the whole [bs, F] block can
    b = pl.program_id(0)
    block = leaf_ref[0]                                  # [bs, F]
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    val = jnp.broadcast_to(val_ref[0].astype(block.dtype), block.shape)
    out_ref[0] = jnp.where(rows == pos_ref[b] % bs, val, block)


def paged_write_token(leaf: jnp.ndarray, tables: jnp.ndarray,
                      positions: jnp.ndarray, values: jnp.ndarray, *,
                      interpret: bool = False) -> jnp.ndarray:
    """Scatter one token per row through the page table.

    leaf [n_blocks+1, block_size, ...]; tables [B, M]; positions [B];
    values [B, ...]. Row b writes into block `tables[b, pos//bs]` at
    offset `pos % bs`; unmapped targets land in the trash block (id 0) —
    identical contract to the XLA `_paged_write` scatter. The leaf is
    input/output aliased: only the touched blocks move through VMEM."""
    N, bs = leaf.shape[:2]
    rest = leaf.shape[2:]
    B, M = tables.shape
    F = int(np.prod(rest)) if rest else 1
    leaf2 = leaf.reshape(N, bs, F)
    # [B, 1, F]: a (1, 1, F) block keeps its last two dims equal to the
    # array's, as the TPU block-shape rule needs
    vals2 = values.reshape(B, 1, F)
    blk = jnp.clip(positions.astype(jnp.int32) // bs, 0, M - 1)
    phys = jnp.take_along_axis(tables.astype(jnp.int32),
                               blk[:, None], axis=1)[:, 0]     # [B]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, F), lambda b, ph, p: (b, 0, 0)),
            pl.BlockSpec((1, bs, F), lambda b, ph, p: (ph[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, F), lambda b, ph, p: (ph[b], 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_write_kernel, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(leaf2.shape, leaf.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(phys, positions.astype(jnp.int32), vals2, leaf2)
    return out.reshape(leaf.shape)
