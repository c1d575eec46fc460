"""Paged flash-decode Pallas kernels (TPU target) + in-kernel paged write.

Decode hot spot of the paged serving backend: one-token attention computed
DIRECTLY against the block-paged KV cache. The XLA fallback in
`models/attention.py` materializes a dense `[B, M*block_size, ...]` view of
every row's pages (`gather_blocks`) before attending — O(B * view) HBM
traffic per decoded token regardless of how much of the view is valid.
These kernels never build that view. Unmapped pages (table entry 0 = the
shared trash block) and pages past a row's `idx <= pos` prefix never reach
the online softmax, whose fp32 (m, l, acc) state lives in VMEM; rows whose
table is all zeros produce exact zeros.

Stacked pools. The serving pool keeps each leaf stacked over the layers of
its group in the kernels' own view, `[L, n_blocks+1, block_size, F]` (GQA:
F = KV*hd; MLA: kv_lora or rope_dim), and the layer scan carries it whole.
Every kernel here takes such a leaf and the layer as a scalar (may be
traced) and reads or writes block `(layer, page)` in place: no layer's
slice of the pool is ever copied out, and no relayout is needed. A single
layer's leaf `[n_blocks+1, block_size, ...]` with no layer reads as a
stack of one.

GQA — paged K/V `[n_blocks+1, block_size, KV, hd]`. The grid is one step
per row; inside it a loop runs over the row's LIVE pages only (through its
last mapped page at or before `pos // block_size`, from the page holding
`pos - window + 1` for a windowed layer), `P` pages per compute
block (`gqa_pages_per_block`: ~256 KB of K per buffer, 8 pages of 16
tokens at 8 KV heads of 128 in bf16). K and V stay in HBM; each live page
is one contiguous `[block_size, KV*hd]` DMA into a double-buffered VMEM
scratch, and the next block's copies (or the next live row's first block)
start before the current block is computed. The math is two 2-D matmuls
per block in the cache dtype with fp32 accumulation: q, spread over the
KV*hd lanes of its own KV head, against the block's `[P*bs, KV*hd]` keys,
then p against the values, which lands each head's context in its own
head's lanes (8x the attention FLOPs, negligible beside the bytes). An
fp32 q or p meets bf16 pages as a bf16 hi/lo pair (`_hi_lo`), so the
products keep ~16 mantissa bits. The work of a call scales with its live
pages, not with `B * M`.

MLA — weight-absorbed decode against the COMPRESSED cache (`ckv`
`[*, kv_lora]` + shared rope key `[*, rope_dim]`): the grid is `(B, M)`,
step (b, j) DMAs ONE `[block_size, ...]` block, `(layer, tables[b, j])`, and
masks it out when unmapped or past `pos`; the kernel applies the kv
rms-norm per block in fp32 and returns the latent context
`[B, 1, H, kv_lora]`; the caller applies W_uv / W_o.

`paged_write_token` is the companion single-token scatter: grid (B,), the
output BlockSpec selects the physical block holding each row's `pos`
through the page table, and the kernel rewrites only `pos % block_size`
(input/output aliased: with a stacked leaf carried through the scan and
donated to the step, the write is in place). Rows whose target
block is unmapped write into the trash block — same contract as the XLA
`_paged_write` they replace. Mapped blocks are pairwise disjoint across
rows (pool invariant), so block revisits only ever hit the trash block.
The GQA decode kernel reads the `[L, N, block_size, KV*hd]` leaf this
kernel writes.

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

# one compute block: about this many bytes of K (and as many of V) per
# buffer, and never more than this many pages (each page is its own DMA)
_BLOCK_BYTES = 256 * 1024
_MAX_BLOCK_PAGES = 16


def gqa_pages_per_block(block_size: int, kv_heads: int, head_dim: int,
                        dtype) -> int:
    """Pages per compute block of the GQA kernel: ~256 KB of K per buffer
    (8 pages of 16 tokens x 8 KV heads x 128 in bf16). Independent of the
    table width, so slicing the table to its active prefix cannot change
    how a row is blocked (nor its result)."""
    page = block_size * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    return int(np.clip(_BLOCK_BYTES // page, 1, _MAX_BLOCK_PAGES))


def _hi_lo(x, dtype):
    """x [n, c] as matmul operands of `dtype`: x itself where it already
    is no wider, else its rounding stacked on the rounding of what that
    left ([2n, c]). A product in `dtype` then keeps ~16 mantissa bits of
    x once `_fold` sums the two halves of the result."""
    hi = x.astype(dtype)
    if jnp.dtype(x.dtype).itemsize <= jnp.dtype(dtype).itemsize:
        return hi
    return jnp.concatenate([hi, (x - hi.astype(x.dtype)).astype(dtype)])


def _fold(r, n: int):
    return r if r.shape[0] == n else r[:n] + r[n:]


def _stacked(leaf, layer):
    """(leaf as `[L, N, bs, F]`, layer as int32 `[1]`). A single layer's
    leaf `[N, bs, ...]` (layer None) gains a leading axis of one and reads
    layer 0. The reshape is free for a leaf already in the kernels'
    layout; a `[.., KV, hd]` leaf pays a relayout."""
    if layer is None:
        leaf, layer = leaf[None], 0
    L, N, bs = leaf.shape[:3]
    return (leaf.reshape(L, N, bs, -1),
            jnp.asarray(layer, jnp.int32).reshape(1))


def _gqa_kernel(tbl_ref, pos_ref, first_ref, live_ref, blk0_ref, nxt_ref,
                lyr_ref, q_ref, k_hbm, v_hbm, o_ref,
                kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
                n_rows: int, M: int, P: int, bs: int, KV: int, group: int,
                scale: float):
    b = pl.program_id(0)
    H, hd = q_ref.shape[2], q_ref.shape[3]
    T = P * bs

    def page(row, blk, i):
        """(page id, live) of page `i` of compute block `blk` of `row`
        (blocks count from the page holding the row's first position):
        live = mapped and before the row's live-page count."""
        col = first_ref[row] // bs + blk * P + i
        pid = tbl_ref[row * M + jnp.minimum(col, M - 1)]
        return pid, (col < live_ref[row]) & (pid > 0)

    def dmas(row, blk, slot, op):
        # one contiguous [bs, KV*hd] copy per live page, K and V each,
        # from the stacked leaf's block (layer, page)
        lyr = lyr_ref[0]

        def one(i, carry):
            pid, live = page(row, blk, i)

            @pl.when(live)
            def _():
                for n, (src, buf) in enumerate(((k_hbm, kbuf),
                                                (v_hbm, vbuf))):
                    getattr(pltpu.make_async_copy(
                        src.at[lyr, pid], buf.at[slot, i],
                        sems.at[n, slot]),
                        op)()
            return carry
        jax.lax.fori_loop(0, P, one, 0)

    @pl.when(b == 0)
    def _():
        # buffer pages no copy reaches still meet p == 0: keep them finite
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

        @pl.when(nxt_ref[0] < n_rows)
        def _():
            dmas(nxt_ref[0], 0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    pos = pos_ref[b]
    # q spread over the KV*hd lanes of its own KV head, zeros elsewhere:
    # one 2-D matmul against the block's [T, KV*hd] keys then gives every
    # head its scores, and p . v lands each head's context in its lanes
    q = q_ref[0, 0]
    kv_of = jax.lax.broadcasted_iota(jnp.int32, (H, hd), 0) // group
    qx = _hi_lo(jnp.concatenate(
        [jnp.where(kv_of == h, q, 0) for h in range(KV)], axis=1),
        kbuf.dtype)                                    # [(2)H, KV*hd]
    lo = first_ref[b] // bs
    n_blk = (jnp.maximum(live_ref[b] - lo, 0) + P - 1) // P

    def body(j, carry):
        slot = (blk0_ref[b] + j) % 2
        # start the next block (this row's, else the next live row's
        # first) before computing this one
        last = j + 1 == n_blk
        nb = jnp.where(last, nxt_ref[b + 1], b)

        @pl.when(nb < n_rows)
        def _():
            dmas(nb, jnp.where(last, 0, j + 1), 1 - slot, "start")

        dmas(b, j, slot, "wait")
        k = kbuf[slot].reshape(T, -1)                          # [T, KV*hd]
        v = vbuf[slot].reshape(T, -1)
        s = _fold(jax.lax.dot_general(
            qx, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), H) * scale    # [H, T]
        # a token counts if it lies in [first, pos] on a page copied for
        # this block: the rest of the buffer holds stale pages or zeros
        tok = jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
        copied = jax.lax.fori_loop(
            0, P, lambda i, ok: jnp.where(
                (tok // bs == i) & page(b, j, i)[1], 1, ok),
            jnp.zeros_like(tok))
        at = (lo + j * P) * bs + tok
        mask = (at <= pos) & (at >= first_ref[b]) & (copied > 0)
        s = jnp.where(mask, s, NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _fold(jnp.dot(
            _hi_lo(p, v.dtype), v, preferred_element_type=jnp.float32), H)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    # each head's context sits in its own KV head's hd lanes
    acc = acc_ref[...]
    out = jnp.zeros((H, hd), jnp.float32)
    for h in range(KV):
        out = out + jnp.where(kv_of == h, acc[:, h * hd:(h + 1) * hd], 0.0)
    o_ref[0, 0] = (out / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_decode_gqa(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, tables: jnp.ndarray,
                           positions: jnp.ndarray, layer=None, *,
                           scale: float | None = None,
                           window=None,
                           interpret: bool = False) -> jnp.ndarray:
    """q [B,1,H,hd]; k/v_pages [n_blocks+1, block_size, KV, hd], or
    stacked over layers `[L, n_blocks+1, block_size, KV*hd]` with `layer`
    (a scalar, may be traced) picking one, read in place;
    tables [B, M] int32 (0 = unmapped); positions [B] int32. Returns
    [B,1,H,hd] — masked-softmax attention over each row's valid prefix,
    equal to the dense-gather path up to fp32 online-softmax rounding
    (bf16 pages with an fp32 q: ~1e-5 of a float64 reference). Rows
    with no mapped page at or before pos return exact zeros. With a
    `window` (an int, or a traced scalar), a row attends to positions in (pos - window, pos] only: its
    walk starts at the page holding pos - window + 1, so it reads at most
    ceil(window / block_size) + 1 pages, and the earlier positions of
    that page are masked."""
    B, T, H, hd = q.shape
    assert T == 1
    k_pages, lyr = _stacked(k_pages, layer)
    v_pages, _ = _stacked(v_pages, layer)
    bs = k_pages.shape[2]
    KV = k_pages.shape[3] // hd
    M = tables.shape[1]
    group = H // KV
    scale = scale or 1.0 / np.sqrt(hd)
    P = gqa_pages_per_block(bs, KV, hd, k_pages.dtype)
    tables = tables.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    # live pages of a row: through its last mapped page at or before
    # pos // bs (0 for an all-trash row); compute blocks are numbered
    # across rows so the double buffer alternates through the whole call
    col = jnp.arange(M, dtype=jnp.int32)
    mapped = (tables > 0) & (col[None, :] <= (positions // bs)[:, None])
    live = jnp.max(jnp.where(mapped, col + 1, 0), axis=1)
    # first position a row attends to; its page starts the walk
    first = (jnp.zeros_like(positions) if window is None else
             jnp.maximum(positions - window + 1, 0).astype(jnp.int32))
    n_blk = (jnp.maximum(live - first // bs, 0) + P - 1) // P
    blk0 = jnp.cumsum(n_blk) - n_blk
    # nxt[b]: the first row >= b with live pages (B if none); nxt[B] = B
    nxt = jax.lax.cummin(jnp.where(n_blk > 0, jnp.arange(B), B),
                         reverse=True)
    nxt = jnp.concatenate([nxt, jnp.full((1,), B, jnp.int32)])
    kernel = functools.partial(_gqa_kernel, n_rows=B, M=M, P=P, bs=bs,
                               KV=KV, group=group, scale=scale)
    row = pl.BlockSpec((1, 1, H, hd),
                       lambda b, tbl, pos, first, live, blk0, nxt, lyr: (
                           b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(B,),
        in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        # the two K/V landing buffers hold the pages' own bytes, as
        # copied; the online-softmax state (m, l, acc) below is fp32
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, KV * hd), k_pages.dtype),  # repro: ignore[PL004]
            pltpu.VMEM((2, P, bs, KV * hd), v_pages.dtype),  # repro: ignore[PL004]
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, KV * hd), jnp.float32)],
    )
    # rows run in order: each prefetches the next live row's first block
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1), positions, first, live, blk0, nxt, lyr, q,
      k_pages, v_pages)


# ---------------------------------------------------------------------------
# MLA paged flash-decode (weight-absorbed, compressed cache)
# ---------------------------------------------------------------------------

def _mla_kernel(tbl_ref, pos_ref, lyr_ref, qa_ref, qr_ref, ckv_ref, kr_ref,
                w_ref, o_ref, m_ref, l_ref, acc_ref, *, n_mb: int, bs: int,
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
                           positions: jnp.ndarray, layer=None, *,
                           scale: float, eps: float = 1e-6,
                           interpret: bool = False) -> jnp.ndarray:
    """Weight-absorbed MLA decode against the paged compressed cache.

    q_abs [B,1,H,r] (q_nope absorbed through W_uk); q_rope [B,1,H,dr];
    ckv_pages [n_blocks+1, bs, r]; kr_pages [n_blocks+1, bs, dr], or both
    stacked over layers (`[L, n_blocks+1, bs, ...]`) with `layer` picking
    one; kv_norm [r]. Returns the latent context [B,1,H,r] in fp32 — the
    caller applies W_uv and W_o (scores AND values stay O(kv_lora))."""
    B, T, H, r = q_abs.shape
    assert T == 1
    dr = q_rope.shape[-1]
    ckv_pages, lyr = _stacked(ckv_pages, layer)
    kr_pages, _ = _stacked(kr_pages, layer)
    bs = ckv_pages.shape[2]
    M = tables.shape[1]
    kernel = functools.partial(_mla_kernel, n_mb=M, bs=bs, scale=scale,
                               eps=eps)
    sq = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, 1, H, r), lambda b, j, t, p, l: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, H, dr), lambda b, j, t, p, l: (b, 0, 0, 0)),
            pl.BlockSpec((sq, 1, bs, r),
                         lambda b, j, t, p, l: (l[0], t[b, j], 0, 0)),
            pl.BlockSpec((sq, 1, bs, dr),
                         lambda b, j, t, p, l: (l[0], t[b, j], 0, 0)),
            pl.BlockSpec((r,), lambda b, j, t, p, l: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, r),
                               lambda b, j, t, p, l: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H,), jnp.float32),
                        pltpu.VMEM((H,), jnp.float32),
                        pltpu.VMEM((H, r), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, r), jnp.float32),
        interpret=interpret,
    )(tables.astype(jnp.int32), positions.astype(jnp.int32), lyr,
      q_abs, q_rope, ckv_pages, kr_pages, kv_norm)


# ---------------------------------------------------------------------------
# In-kernel single-token paged write
# ---------------------------------------------------------------------------

def _write_kernel(phys_ref, pos_ref, lyr_ref, val_ref, leaf_ref, out_ref, *,
                  bs: int):
    # masked whole-block write: a store at a dynamic row of a packed bf16
    # tile cannot be proven tile-aligned by the TPU compiler, a select
    # over the whole [bs, F] block can
    b = pl.program_id(0)
    block = leaf_ref[0]                                  # [bs, F]
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    val = jnp.broadcast_to(val_ref[0].astype(block.dtype), block.shape)
    out_ref[0] = jnp.where(rows == pos_ref[b] % bs, val, block)


def paged_write_token(leaf: jnp.ndarray, tables: jnp.ndarray,
                      positions: jnp.ndarray, values: jnp.ndarray,
                      layer=None, *, interpret: bool = False) -> jnp.ndarray:
    """Scatter one token per row through the page table.

    leaf [n_blocks+1, block_size, ...], or stacked over layers
    `[L, n_blocks+1, block_size, F]` with `layer` (a scalar, may be
    traced) picking one; tables [B, M]; positions [B]; values [B, ...].
    Row b writes into block `(layer, tables[b, pos//bs])` at offset
    `pos % bs`; unmapped targets land in the trash block (id 0) —
    identical contract to the XLA `_paged_write` scatter. The leaf is
    input/output aliased: only the touched blocks move through VMEM, and
    a stacked leaf keeps its shape (the other layers are untouched)."""
    shape = leaf.shape
    leaf4, lyr = _stacked(leaf, layer)
    bs, F = leaf4.shape[2:]
    B, M = tables.shape
    # [B, 1, F]: a (1, 1, F) block keeps its last two dims equal to the
    # array's, as the TPU block-shape rule needs
    vals2 = values.reshape(B, 1, F)
    blk = jnp.clip(positions.astype(jnp.int32) // bs, 0, M - 1)
    phys = jnp.take_along_axis(tables.astype(jnp.int32),
                               blk[:, None], axis=1)[:, 0]     # [B]
    sq = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, F), lambda b, ph, p, l: (b, 0, 0)),
            pl.BlockSpec((sq, 1, bs, F),
                         lambda b, ph, p, l: (l[0], ph[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((sq, 1, bs, F),
                               lambda b, ph, p, l: (l[0], ph[b], 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_write_kernel, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(leaf4.shape, leaf.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(phys, positions.astype(jnp.int32), lyr, vals2, leaf4)
    return out.reshape(shape)
