"""Block-paged KV-cache pool for continuous batching with ragged prompts.

`SlotCachePool` reserves a worst-case `max_len` row per slot, so a short
request pays for the longest request's memory and mixed-length traffic
caps batch size. `PagedCachePool` instead stores the cache in fixed-size
**blocks** of `block_size` tokens shared by all slots:

  * every attention-cache leaf becomes ``[L, n_blocks + 1, block_size, F]``
    — stacked over its layer group, features flat (GQA: F = KV*head_dim;
    MLA: kv_lora or rope_dim), the paged kernels' own view, so the
    jitted programs update it in place with no relayout (the decode step
    and the prefill chunk take it donated). Physical block 0 is a shared
    **trash block** that is never allocated — unmapped page-table
    entries and writes from inactive decode rows land there harmlessly;
  * each slot owns a row of the **page table** ``[n_slots, M]`` mapping
    its logical block ``m`` (tokens ``[m*block_size, (m+1)*block_size)``)
    to a physical block id, 0 meaning unmapped;
  * blocks are mapped on demand as a request's prefill/decode frontier
    advances and returned to the free list at retirement.

Admission control is **reservation-based**: admitting a request reserves
its worst-case block count ``ceil((prompt_len + max_new - 1)/block_size)``
(its prompt plus every decode token it may produce), but blocks are only
*mapped* lazily. With ``oversubscribe == 1`` (the default) the invariant
``free >= reserved`` guarantees that `ensure_mapped` never fails
mid-flight, so no preemption path is needed; the per-request worst case
is still far below the slot pool's global worst case on ragged traffic,
which is the memory win this pool exists for.

**Oversubscription.** ``oversubscribe > 1`` relaxes the reservation
invariant to a *virtual* budget: admission may reserve up to
``round(n_blocks * oversubscribe)`` blocks against only ``n_blocks``
physical ones, betting that most requests retire before their worst
case. The generalized invariant is ``physical_in_use + reserved_total
<= virtual_blocks`` (algebraically identical to ``free >= reserved``
at factor 1). The price: `ensure_mapped` / `cow_clone` can now hit
genuine physical exhaustion mid-flight, surfaced as the typed
:class:`BlockPressure` exception — the engine's `PressurePolicy`
(serving/pressure.py) answers it by preempting, deferring, or shedding
a victim. Without oversubscription exhaustion is still a hard
RuntimeError (a bookkeeping bug, not pressure).

**Host swap tier.** ``swap_blocks > 0`` gives evicted cached prefix
blocks a second life: when `_pop_free` must evict a zero-ref registered
block, its contents are first copied to a bounded host-RAM store (LRU
over chain keys, capacity ``swap_blocks``). `share_prefix` consults the
store after the device registry misses and swaps matching blocks back
in (`_swap_in`: allocate + host->device copy + re-register), so the
prefix cache survives pressure instead of being recomputed. Swap keys
and device registry keys are always disjoint.

**Prefix sharing (copy-on-write).** Physical blocks carry reference
counts, so one block may appear in several slots' tables. A **prefix
registry** keys each fully-prefilled prompt block on the hash chain of
its token ids (`prefix_block_keys`); `share_prefix` maps the longest
registered prefix of a new request's prompt straight into its table —
those tokens are never prefilled again. Shared blocks are read-only:
any write first goes through `ensure_writable`, which `cow_clone`s a
block whose refcount exceeds one into a fresh private copy, so the
paged write paths (`models.attention._paged_write` and the Pallas
`paged_write_token` kernel) keep their "writable blocks are pairwise
disjoint across rows" contract — `check_write_disjoint` asserts it per
dispatch. Reservation accounting covers the CoW worst case (sharing
gives the matched blocks' reservation back, withholding one block of
slack exactly when a fully-shared prompt could clone its tail), so
`ensure_mapped` and `cow_clone` stay infallible for admitted requests.
Released blocks whose refcount hits zero keep their registry entry
("cached"): their content is intact until reallocated, so a later
same-prefix request can resurrect them even after the donor retired.
Allocation prefers unregistered free blocks (lowest id first) and
evicts cached ones — also lowest id first — only when it must.

Invariants (pinned by tests/test_serving_paged.py and
tests/test_serving_prefix.py):
  * per-slot table rows never repeat a block and never map block 0;
  * every block's refcount equals the number of table rows of in-use
    slots that map it; blocks with refcount 0 are exactly the free set;
  * refcounted + free is always exactly {1..n_blocks};
  * len(free) >= total outstanding reservation;
  * registry entries point at blocks that are mapped or cached-free,
    bijectively with the reverse map;
  * a slot's table row is all-zero whenever the slot is free.

Families whose cache carries state without a ``cache_seq`` axis (RWKV,
Mamba) or with a sliding-window ring shorter than the sequence cannot be
paged; construction raises with a clear message.
"""
from __future__ import annotations

import hashlib
import heapq
import math
from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.models import transformer as tfm
from repro.models.attention import gather_blocks
from repro.serving.cache_pool import _is_abstract


class BlockPressure(RuntimeError):
    """Physical block exhaustion under oversubscription.

    Raised by allocation paths (`ensure_mapped` -> `_take_free_block`,
    `cow_clone`, swap-in) when the pool is oversubscribed and no
    physical block is free — an expected, recoverable condition the
    engine answers with its pressure policy (preempt / defer-on-OOM /
    shed a victim, then retry). Never raised at ``oversubscribe == 1``,
    where the reservation invariant makes allocation infallible and
    exhaustion stays a hard RuntimeError."""


def next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the shared bucketing policy
    for jit-shape control (active-prefix table slicing, batched prefill
    dispatch width): O(log n) distinct shapes ever compile."""
    p = 1
    while p < n:
        p *= 2
    return p


def prefix_block_keys(tokens: np.ndarray, block_size: int) -> List[bytes]:
    """Hash-chain keys of every FULL block of `tokens`.

    Key m digests (key of block m-1, tokens of block m), so equal keys
    mean equal whole prefixes, not just equal blocks — the registry can
    match block-granular longest prefixes without storing token arrays.
    Only fully-populated blocks get keys: a partial tail block is still
    written by its owner's prefill/decode and must never be shared."""
    out: List[bytes] = []
    prev = b""
    toks = np.ascontiguousarray(tokens, np.int32)
    for m in range(len(toks) // block_size):
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(toks[m * block_size:(m + 1) * block_size].tobytes())
        prev = h.digest()
        out.append(prev)
    return out


def validate_pageable(cfg: ModelConfig, max_len: int) -> None:
    """Raise NotImplementedError unless every cache leaf is a linear
    attention cache (has a full-length ``cache_seq`` axis). Windowed
    layers of a model with per-layer attention kinds (``layer_kinds``)
    pass: they keep every page of a row and mask by position; only a
    config-wide ``sliding_window`` ring cache is refused."""
    abstract = tfm.init_cache(cfg, 1, max_len, abstract=True)
    for leaf in jax.tree.leaves(abstract, is_leaf=_is_abstract):
        axes = leaf.logical_axes
        if "cache_seq" not in axes:
            raise NotImplementedError(
                f"paged KV cache requires attention caches only; leaf with "
                f"axes {axes} (recurrent state?) cannot be paged — use the "
                f"slot backend for family {cfg.family!r}")
        if leaf.shape[axes.index("cache_seq")] != max_len:
            raise NotImplementedError(
                f"paged KV cache does not support windowed/ring caches "
                f"(leaf seq {leaf.shape[axes.index('cache_seq')]} != "
                f"max_len {max_len}); use the slot backend")


def paged_layout(cfg: ModelConfig, n_blocks: int, block_size: int,
                 dtype=None) -> Tuple[Any, Any]:
    """(shapes, block axes) of a paged pool's leaves: `init_cache` with
    batch = blocks gives `[L, n_blocks+1, block_size, ...]` per leaf (block
    0 = trash); the features after `block_size` are flattened into the
    kernels' view `[L, n_blocks+1, block_size, F]`. `shapes` is a tree of
    `jax.ShapeDtypeStruct`, so a pool's programs can be compiled without
    one."""
    dtype = dtype or cfg.cdtype()
    abstract = tfm.init_cache(cfg, n_blocks + 1, block_size, abstract=True)

    def _axes(a):
        b = a.logical_axes.index("batch")
        s = a.logical_axes.index("cache_seq")
        assert s == b + 1, "paged gather assumes [block, block_size] adjacency"
        return b
    axes = jax.tree.map(_axes, abstract, is_leaf=_is_abstract)
    shapes = jax.tree.map(
        lambda a, b: jax.ShapeDtypeStruct(
            a.shape[:b + 2] + (math.prod(a.shape[b + 2:]),), dtype),
        abstract, axes, is_leaf=_is_abstract)
    return shapes, axes


def gather_pages(cache: Any, tables: jnp.ndarray, block_axes: Any) -> Any:
    """Tree-wide page-table gather: paged cache -> dense per-slot view
    ``[..., n_slots_in_tables, M*block_size, F]`` (features flat, as the
    pool keeps them). Host-side test/debug helper; the jitted paths
    gather leaf-wise inside attention."""
    def one(leaf, ax):
        if ax == 0:
            return gather_blocks(leaf, tables)
        assert ax == 1, "block axis beyond [layers] leading dim unsupported"
        return jax.vmap(lambda l: gather_blocks(l, tables))(leaf)
    return jax.tree.map(one, cache, block_axes)


class PagedCachePool:
    """Block-paged per-slot cache + slot/block/reservation bookkeeping.

    Device state: ``.cache`` (paged leaves, replaced functionally after
    each jitted step) and ``.tables_device()`` (the int32 page table the
    jitted programs index through). Host state: free lists, per-slot
    mapped/reserved counts, per-block refcounts, the prefix registry,
    lifetime counters.

    The **slot** API (`alloc`/`release`/`n_free`/`in_use`) matches
    `SlotCachePool`, so `SlotScheduler` drives either pool; the **block**
    API (`can_reserve`/`reserve`/`ensure_mapped`) is what makes admission
    ragged-aware; the **sharing** API (`share_prefix`/`register_prefix`/
    `ensure_writable`/`cow_clone`/`check_write_disjoint`) is what lets
    slots alias read-only prompt blocks safely.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, n_blocks: int,
                 block_size: int, max_len: int, dtype=None,
                 oversubscribe: float = 1.0, swap_blocks: int = 0):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if block_size < 1 or n_blocks < 1:
            raise ValueError("block_size and n_blocks must be >= 1")
        if oversubscribe < 1.0:
            raise ValueError(f"oversubscribe must be >= 1.0, "
                             f"got {oversubscribe}")
        if swap_blocks < 0:
            raise ValueError(f"swap_blocks must be >= 0, got {swap_blocks}")
        validate_pageable(cfg, max_len)
        self.cfg = cfg
        self.n_slots = n_slots
        self.n_blocks = n_blocks
        self.block_size = block_size
        self.max_len = max_len
        # logical blocks a single slot may address (covers max_len plus
        # one block of slack for padded-chunk clamping)
        self.max_blocks = math.ceil(max_len / block_size) + 1
        shapes, self.block_axes = paged_layout(cfg, n_blocks, block_size,
                                               dtype)
        self.cache = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                  shapes)
        # dispatches, by program, that updated the pool in place (their
        # donated leaves read deleted after the call): `count_inplace`
        self.inplace_total = {"step": 0, "prefill": 0}

        # host bookkeeping
        self.tables = np.zeros((n_slots, self.max_blocks), np.int32)
        self.n_mapped = np.zeros(n_slots, np.int64)
        self.ref = np.zeros(n_blocks + 1, np.int64)  # per-block refcount
        self._owed = np.zeros(n_slots, np.int64)     # reserved, not yet mapped
        self._reserved_total = 0
        # free blocks: two min-heaps with a lazy-deletion membership set —
        # plain (never registered) preferred over cached (registered: a
        # retired prefix whose content is still intact). Deterministic:
        # lowest id first within each class.
        self._free_plain: List[int] = list(range(1, n_blocks + 1))
        self._free_cached: List[int] = []
        self._free_set: set = set(self._free_plain)
        self._free_slots: List[int] = list(range(n_slots))  # min-heap
        self._in_use: set = set()
        # prefix registry: chain key -> physical block, plus reverse map
        self._prefix_registry: Dict[bytes, int] = {}
        self._registered_key: Dict[int, bytes] = {}
        self.generations = [0] * n_slots
        self.peak_mapped = 0           # high-water PHYSICAL blocks in use
        self.shared_blocks_total = 0   # lifetime blocks mapped via sharing
        self.cow_clones = 0            # lifetime copy-on-write clones
        # oversubscription: virtual reservation budget (== n_blocks at
        # factor 1, where the classic free >= reserved invariant holds)
        self.oversubscribe = float(oversubscribe)
        self.virtual_blocks = int(round(n_blocks * self.oversubscribe))
        # host swap tier: chain key -> host copy of the block's cache
        # leaves (insertion-ordered for LRU eviction), bounded capacity
        self.swap_blocks = int(swap_blocks)
        self._swap: "OrderedDict[bytes, Any]" = OrderedDict()
        self.swap_outs = 0             # lifetime device -> host spills
        self.swap_ins = 0              # lifetime host -> device restores
        self._tables_dev = jnp.asarray(self.tables)
        self._tables_prefix_cache: dict = {}
        self._tables_dirty = False

    # -- capacity / accounting --------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """Physical blocks needed to store `n_tokens` cache entries."""
        return max(0, math.ceil(n_tokens / self.block_size))

    @property
    def n_free_blocks(self) -> int:
        return len(self._free_set)

    @property
    def n_physical_in_use(self) -> int:
        """Physical blocks currently allocated (refcount > 0). Shared
        blocks count ONCE — this is the footprint number."""
        return self.n_blocks - len(self._free_set)

    @property
    def n_mapped_total(self) -> int:
        """Total table entries over in-use slots (shared blocks count
        once per slot mapping them)."""
        return int(self.n_mapped.sum())

    def footprint_bytes(self) -> int:
        """Device bytes held by the paged cache (all physical blocks)."""
        return sum(l.nbytes for l in jax.tree.leaves(self.cache))

    # -- slot bookkeeping (SlotCachePool-compatible) ----------------------
    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def in_use(self) -> frozenset:
        return frozenset(self._in_use)

    def alloc(self) -> int:
        """Lowest-numbered free slot (deterministic placement)."""
        if not self._free_slots:
            raise RuntimeError("cache pool exhausted")
        slot = heapq.heappop(self._free_slots)
        self._in_use.add(slot)
        self.generations[slot] += 1
        return slot

    def release(self, slot: int,
                expected_generation: Optional[int] = None) -> None:
        """Free the slot: decrement its blocks' refcounts — only blocks
        that hit zero return to the free list (a block still shared by
        another slot lives on; a zero-ref block that is REGISTERED keeps
        its registry entry and goes to the cached free heap, reusable by
        a later same-prefix request until evicted) — drop its
        outstanding reservation, and zero its table row (so stale decode
        writes from the retired tenant land in the trash block).

        Double release is LOUD, never silent: releasing a slot that is
        not in use raises with the slot id (instead of pushing its
        blocks onto the free heap twice and corrupting refcounts), and
        `expected_generation` (the value of ``generations[slot]`` the
        caller captured at alloc) catches the nastier stale-release case
        where the slot was already re-allocated to a new tenant."""
        if slot not in self._in_use:
            raise RuntimeError(
                f"double release of slot {slot}: slot is not in use "
                f"(already released or never allocated) — a second "
                f"release would corrupt the free-block heap")
        if (expected_generation is not None
                and expected_generation != self.generations[slot]):
            raise RuntimeError(
                f"stale release of slot {slot}: caller holds generation "
                f"{expected_generation} but the slot was re-allocated "
                f"(now generation {self.generations[slot]}) — releasing "
                f"would free the new tenant's blocks")
        self._in_use.remove(slot)
        heapq.heappush(self._free_slots, slot)
        for m in range(int(self.n_mapped[slot])):
            blk = int(self.tables[slot, m])
            self.ref[blk] -= 1
            if self.ref[blk] == 0:
                self._push_free(blk)
        self._reserved_total -= int(self._owed[slot])
        self._owed[slot] = 0
        self.n_mapped[slot] = 0
        self.tables[slot] = 0
        self._tables_dirty = True

    # -- free-block heaps (lazy deletion) ----------------------------------
    def _push_free(self, blk: int) -> None:
        heap = (self._free_cached if blk in self._registered_key
                else self._free_plain)
        heapq.heappush(heap, blk)
        self._free_set.add(blk)

    def _pop_free(self) -> int:
        """Lowest-id unregistered free block, else evict (deregister) the
        lowest-id cached one — spilling its contents to the host swap
        store first when one is configured. Caller owns the block (ref
        set to 1). Exhaustion raises `BlockPressure` when oversubscribed
        (recoverable: the engine's pressure policy frees a victim), a
        hard RuntimeError otherwise (the reservation invariant makes it
        a bookkeeping bug)."""
        for heap in (self._free_plain, self._free_cached):
            while heap:
                blk = heapq.heappop(heap)
                if blk not in self._free_set:
                    continue                    # stale lazy-deleted entry
                self._free_set.remove(blk)
                key = self._registered_key.pop(blk, None)
                if key is not None:             # evict the cached prefix
                    del self._prefix_registry[key]
                    if self.swap_blocks > 0:
                        self._swap_out(blk, key)
                self.ref[blk] = 1
                return blk
        if self.virtual_blocks > self.n_blocks:
            raise BlockPressure(
                f"paged pool out of physical blocks ({self.n_blocks} "
                f"in use, {self._reserved_total} still reserved) under "
                f"oversubscription x{self.oversubscribe:g}")
        raise RuntimeError("paged pool out of blocks — reservation "
                           "invariant violated")

    def _take_free_block(self, slot: int) -> int:
        """Allocate one fresh block for `slot`, charged against its
        reservation — or, beyond it, against UNRESERVED virtual headroom.
        The over-map case raises rather than silently draining blocks
        that other slots' reservations are counting on. Reservation
        accounting is only charged AFTER the pop succeeds, so a
        `BlockPressure` raise leaves the books untouched and the caller
        can retry the same demand after relieving pressure."""
        charged = self._owed[slot] > 0
        if not charged and (self.n_physical_in_use + 1
                            + self._reserved_total > self.virtual_blocks):
            raise RuntimeError(
                f"slot {slot} mapping beyond its reservation would leave "
                f"free ({len(self._free_set) - 1}) < reserved "
                f"({self._reserved_total}) — raise n_blocks or reserve "
                f"the slack explicitly")
        blk = self._pop_free()
        if charged:
            self._owed[slot] -= 1
            self._reserved_total -= 1
        self.peak_mapped = max(self.peak_mapped, self.n_physical_in_use)
        return blk

    # -- block reservation / mapping --------------------------------------
    def can_reserve(self, n_tokens: int) -> bool:
        """True if a request needing `n_tokens` total cache entries can be
        admitted within the (possibly oversubscribed) reservation budget:
        ``physical_in_use + reserved_total + need <= virtual_blocks``.
        At ``oversubscribe == 1`` this is exactly the classic
        ``free - reserved >= need`` check, under which an admitted
        request can never starve; beyond 1 it is a bet that `ensure_
        mapped` may lose (`BlockPressure`) and the engine must cover."""
        return (self.n_physical_in_use + self._reserved_total
                + self.blocks_for(n_tokens) <= self.virtual_blocks)

    def reserve(self, slot: int, n_tokens: int) -> None:
        """Reserve the slot's worst-case block count. Must hold
        `can_reserve(n_tokens)`; blocks are mapped later by
        `ensure_mapped` (or aliased by `share_prefix`, which returns the
        matched blocks' share of this reservation)."""
        need = self.blocks_for(n_tokens)
        if (self.n_physical_in_use + self._reserved_total + need
                > self.virtual_blocks):
            raise RuntimeError("paged pool over-reserved: admission must "
                               "check can_reserve() first")
        self._owed[slot] = need
        self._reserved_total += need

    def ensure_mapped(self, slot: int, n_tokens: int) -> int:
        """Map blocks until the slot covers `n_tokens` logical cache
        entries. At ``oversubscribe == 1`` this never fails for demands
        within the slot's reservation (the free list always holds >=
        reserved blocks); oversubscribed pools may raise `BlockPressure`
        mid-way — already-mapped progress is kept and the call is
        idempotent, so the caller retries the same demand after its
        pressure policy frees blocks. Returns the number of newly
        mapped blocks."""
        need = self.blocks_for(n_tokens)
        newly = 0
        while int(self.n_mapped[slot]) < need:
            blk = self._take_free_block(slot)
            m = int(self.n_mapped[slot])
            self.tables[slot, m] = blk
            self.n_mapped[slot] += 1
            newly += 1
        if newly:
            self._tables_dirty = True
        return newly

    # -- host swap tier -----------------------------------------------------
    def _swap_out(self, blk: int, key: bytes) -> None:
        """Spill an evicted cached block's contents to the host store
        before its device block is handed to a new owner. The store is
        an LRU over chain keys bounded by `swap_blocks`; the copy is a
        plain `np.asarray` pull of every cache leaf's block row (jax
        dispatches the device->host transfers asynchronously; the arrays
        materialize lazily on first host access)."""
        def one(leaf, ax):
            if ax == 0:
                return np.asarray(leaf[blk])
            return np.asarray(leaf[:, blk])
        self._swap[key] = jax.tree.map(one, self.cache, self.block_axes)
        self._swap.move_to_end(key)
        self.swap_outs += 1
        while len(self._swap) > self.swap_blocks:
            self._swap.popitem(last=False)      # LRU: drop the coldest

    def _swap_in(self, slot: int, key: bytes) -> int:
        """Restore a swapped-out prefix block: allocate a device block
        (charged to `slot`'s reservation like a fresh mapping — may
        raise `BlockPressure`), copy the host contents back, and
        re-register the chain key. Returns the new physical id with
        ref already set to 1."""
        blk = self._take_free_block(slot)        # BlockPressure-able
        host = self._swap.pop(key)

        def one(leaf, hv, ax):
            if ax == 0:
                return leaf.at[blk].set(hv)
            return leaf.at[:, blk].set(hv)
        self.cache = jax.tree.map(one, self.cache, host, self.block_axes)
        self._prefix_registry[key] = blk
        self._registered_key[blk] = key
        self.swap_ins += 1
        return blk

    def save_block_span(self, slot: int, lo: int, hi: int) -> list:
        """Host snapshot of the physical blocks covering token span
        [lo, hi) of `slot` (whole blocks; `lo` rounds down to a block
        boundary). The preemption path uses this for the DECODE-written
        region of a victim's cache: decode-written K/V is not bit-
        identical to a prefill recompute of the same positions (different
        reduction shapes), so those bytes must survive preemption
        verbatim — unlike prompt blocks, which chunked prefill recomputes
        bit-exactly. Returns an opaque list for `restore_block_span`."""
        if hi <= lo:
            return []
        saved = []
        for m in range(lo // self.block_size,
                       (hi - 1) // self.block_size + 1):
            blk = int(self.tables[slot, m])

            def one(leaf, ax, blk=blk):
                if ax == 0:
                    return np.asarray(leaf[blk])
                return np.asarray(leaf[:, blk])
            saved.append(jax.tree.map(one, self.cache, self.block_axes))
        return saved

    def restore_block_span(self, slot: int, lo: int, hi: int,
                           saved: list) -> None:
        """Write a `save_block_span` snapshot back over the SAME token
        span of `slot`'s (re-mapped) table. The span's blocks must be
        mapped and write-private — the resume path maps them fresh, so
        they are; restoring over a shared or registered block would
        corrupt another reader."""
        if hi <= lo:
            return
        ms = range(lo // self.block_size, (hi - 1) // self.block_size + 1)
        for m, host in zip(ms, saved):
            blk = int(self.tables[slot, m])
            assert blk > 0 and self.ref[blk] == 1 \
                and blk not in self._registered_key, \
                (f"restore_block_span: slot {slot} block {m} (phys {blk}) "
                 f"is not a private mapped block")

            def one(leaf, hv, ax, blk=blk):
                if ax == 0:
                    return leaf.at[blk].set(hv)
                return leaf.at[:, blk].set(hv)
            self.cache = jax.tree.map(one, self.cache, host,
                                      self.block_axes)

    # -- prefix sharing / copy-on-write ------------------------------------
    def share_prefix(self, slot: int, tokens: np.ndarray) -> int:
        """Map the longest registered prefix of `tokens` into `slot`'s
        table without prefilling it. Must run right after `reserve`,
        before any `ensure_mapped` for the slot (shared blocks occupy
        the leading table entries). Returns the number of prompt tokens
        covered (a multiple of `block_size`; the caller starts prefill
        at the first unshared token).

        Matched blocks that are still refcounted are aliased (ref+1);
        matched blocks sitting cached on the free list are resurrected
        (ref 0 -> 1, leaving the free list, charged to the slot's
        reservation like a fresh mapping); keys missing from the device
        registry but present in the host swap store are swapped back in
        (`_swap_in` — on `BlockPressure` matching simply stops at the
        blocks already recovered). Aliased blocks give their reservation
        back — minus ONE block of slack when the prompt is fully shared
        with an aliased tail, so the worst-case `cow_clone` (a
        fully-shared prompt recomputes its final token in place) can
        never fail. Partial shares restart prefill at a block boundary
        and never write shared blocks, so they keep no slack."""
        assert int(self.n_mapped[slot]) == 0, \
            "share_prefix needs an empty table row"
        keys = prefix_block_keys(tokens, self.block_size)
        shared = 0
        aliased = 0
        for m, key in enumerate(keys):
            blk = self._prefix_registry.get(key)
            if blk is None:
                if self.swap_blocks <= 0 or key not in self._swap:
                    break
                try:
                    blk = self._swap_in(slot, key)
                except BlockPressure:
                    break        # keep what we recovered; caller prefills
            elif self.ref[blk] == 0:
                # cached free block: resurrect (consumes a free block,
                # so it is charged like a fresh mapping — or, past the
                # reservation, only within virtual headroom)
                if self._owed[slot] <= 0 and (
                        self.n_physical_in_use + 1 + self._reserved_total
                        > self.virtual_blocks):
                    break
                self._free_set.remove(blk)
                if self._owed[slot] > 0:
                    self._owed[slot] -= 1
                    self._reserved_total -= 1
                self.ref[blk] = 1
            else:
                self.ref[blk] += 1
                aliased += 1
            self.tables[slot, m] = blk
            self.n_mapped[slot] += 1
            shared += 1
        if shared:
            # aliased blocks consumed no free block: return their owed
            # share. Only a FULLY-shared prompt can ever CoW-clone (its
            # final token is recomputed inside the last shared block; a
            # partial share restarts prefill at a block boundary, so no
            # write ever targets a shared block) — withhold one owed
            # block of slack exactly when that clone is possible: full
            # cover AND a tail block that is still aliased (ref > 1).
            give = aliased
            if (shared * self.block_size >= len(tokens)
                    and self.ref[int(self.tables[slot, shared - 1])] > 1):
                give -= 1
            give = min(max(give, 0), int(self._owed[slot]))
            self._owed[slot] -= give
            self._reserved_total -= give
            self.shared_blocks_total += shared
            self._tables_dirty = True
            self.peak_mapped = max(self.peak_mapped, self.n_physical_in_use)
        return shared * self.block_size

    def register_prefix(self, slot: int, tokens: np.ndarray) -> int:
        """Publish `slot`'s fully-prefilled prompt blocks so later
        requests can `share_prefix` them. Call once the prompt's K/V
        writes have all been dispatched (the engine does it at
        prefill-done). First registration of a key wins; blocks whose
        content chain is already registered (e.g. the donor's own shared
        prefix, or a CoW clone that rewrote identical values) are
        skipped. Returns how many entries were added."""
        n = 0
        for m, key in enumerate(prefix_block_keys(tokens, self.block_size)):
            if m >= int(self.n_mapped[slot]):
                break
            blk = int(self.tables[slot, m])
            if key in self._prefix_registry or blk in self._registered_key:
                continue
            self._prefix_registry[key] = blk
            self._registered_key[blk] = key
            # a device registration supersedes any stale host copy (swap
            # keys and registry keys stay disjoint)
            self._swap.pop(key, None)
            n += 1
        return n

    def cow_clone(self, slot: int, m: int) -> int:
        """Copy-on-write: replace `slot`'s logical block `m` — currently
        aliased by another slot — with a private copy of its contents.
        The clone is charged to the slot's reservation (see
        `share_prefix`'s CoW slack); the original keeps its refcount
        minus one and its registry entry. Returns the new physical id."""
        old = int(self.tables[slot, m])
        assert old > 0 and self.ref[old] > 1, \
            f"cow_clone: slot {slot} block {m} (phys {old}) is not shared"
        new = self._take_free_block(slot)
        self.ref[old] -= 1
        self.tables[slot, m] = new
        self._tables_dirty = True
        self.cow_clones += 1

        def copy(leaf, ax):
            if ax == 0:
                return leaf.at[new].set(leaf[old])
            return leaf.at[:, new].set(leaf[:, old])
        self.cache = jax.tree.map(copy, self.cache, self.block_axes)
        return new

    def ensure_writable(self, slot: int, lo: int, hi: int) -> int:
        """CoW-clone every mapped block of `slot` holding a logical
        position in [lo, hi) whose refcount exceeds one. Writes beyond
        the mapped frontier land in the trash block and need no clone.
        Call before ANY write dispatch targeting those positions —
        afterwards the slot's writable table entries are private.
        Returns the number of clones made."""
        if hi <= lo:
            return 0
        m_lo = lo // self.block_size
        m_hi = min((hi - 1) // self.block_size, int(self.n_mapped[slot]) - 1)
        n = 0
        for m in range(m_lo, m_hi + 1):
            blk = int(self.tables[slot, m])
            if blk > 0 and self.ref[blk] > 1:
                self.cow_clone(slot, m)
                n += 1
        return n

    def check_write_disjoint(self,
                             ranges: Iterable[Tuple[int, int, int]]) -> None:
        """Assert that the physical blocks writable by a single dispatch
        are pairwise disjoint across rows. `ranges` is (slot, lo, hi)
        token spans about to be written (one per dispatch row). Both
        paged write paths — the XLA `_paged_write` scatter and the
        input/output-aliased `paged_write_token` kernel — assume this;
        an aliased writable block means a missed `ensure_writable` and
        would silently corrupt a neighbor's cache. Trash-block targets
        (unmapped tail positions) are exempt."""
        owner: Dict[int, int] = {}
        for slot, lo, hi in ranges:
            if hi <= lo:
                continue
            m_hi = min((hi - 1) // self.block_size,
                       int(self.n_mapped[slot]) - 1)
            for m in range(lo // self.block_size, m_hi + 1):
                blk = int(self.tables[slot, m])
                if blk == 0:
                    continue
                prev = owner.get(blk)
                if prev is not None and prev != slot:
                    raise RuntimeError(
                        f"paged write aliasing: block {blk} is writable "
                        f"from slots {prev} and {slot} in one dispatch — "
                        f"CoW guard failed (ensure_writable not called?)")
                owner[blk] = slot

    # -- observability ------------------------------------------------------
    @property
    def n_shared_blocks(self) -> int:
        """Physical blocks currently aliased by more than one slot."""
        return int((self.ref > 1).sum())

    @property
    def n_cached_blocks(self) -> int:
        """Zero-ref blocks still holding a registered prefix (reusable
        by a later same-prefix request until evicted)."""
        return sum(1 for b in self._registered_key if b in self._free_set)

    @property
    def n_swapped_blocks(self) -> int:
        """Prefix blocks currently living only in the host swap store."""
        return len(self._swap)

    def register_metrics(self, reg) -> None:
        """Expose pool occupancy as pull-mode gauges on a
        `MetricsRegistry` — callbacks are evaluated only at scrape or
        render time, so the allocation hot paths pay nothing."""
        g = reg.gauge("serving_pool_blocks",
                      "paged KV pool physical blocks by state", ("kind",))
        g.labels(kind="total").set_fn(lambda: self.n_blocks)
        g.labels(kind="free").set_fn(lambda: len(self._free_set))
        g.labels(kind="reserved").set_fn(lambda: self._reserved_total)
        g.labels(kind="in_use").set_fn(lambda: self.n_physical_in_use)
        g.labels(kind="refcounted").set_fn(lambda: self.n_shared_blocks)
        g.labels(kind="cached").set_fn(lambda: self.n_cached_blocks)
        g.labels(kind="peak").set_fn(lambda: self.peak_mapped)
        g.labels(kind="virtual").set_fn(lambda: self.virtual_blocks)
        g.labels(kind="swapped").set_fn(lambda: self.n_swapped_blocks)
        reg.gauge("serving_pool_cow_clones_total",
                  "lifetime copy-on-write block clones",
                  fn=lambda: self.cow_clones)
        reg.gauge("serving_pool_shared_blocks_total",
                  "lifetime blocks mapped via prefix sharing",
                  fn=lambda: self.shared_blocks_total)
        s = reg.gauge("serving_pool_swap_total",
                      "lifetime host swap-tier transfers", ("dir",))
        s.labels(dir="out").set_fn(lambda: self.swap_outs)
        s.labels(dir="in").set_fn(lambda: self.swap_ins)

        i = reg.gauge("serving_pool_inplace_total",
                      "dispatches that updated the pool in place (XLA "
                      "honoured the donation)", ("fn",))
        for fn in self.inplace_total:
            i.labels(fn=fn).set_fn(lambda fn=fn: self.inplace_total[fn])

    def count_inplace(self, fn: str, donated) -> None:
        """Count a dispatch of program `fn` ("step" or "prefill") that
        updated the pool in place: XLA honoured the donation when every
        leaf handed to it (`donated`, the pool's leaves before the call)
        reads deleted afterwards. A host check, no device sync; a count
        below the dispatches means a program copied the pool."""
        if all(a.is_deleted() for a in donated):
            self.inplace_total[fn] += 1

    def active_prefix_blocks(self, n_tokens: int) -> int:
        """Logical blocks needed to cover `n_tokens` cache entries,
        bucketed UP to a power of two (and clamped to `max_blocks`) so
        table-prefix slicing compiles only O(log max_blocks) shapes.
        The decode paths gather/walk only this prefix instead of all
        `max_blocks` table entries — the active-prefix tightening."""
        return min(next_pow2(self.blocks_for(n_tokens)), self.max_blocks)

    def tables_device(self, prefix: Optional[int] = None) -> jnp.ndarray:
        """Device copy of the page table, refreshed only when the host
        table changed since the last call. `prefix` returns only the
        first `prefix` logical-block columns (see
        `active_prefix_blocks`); each distinct prefix is cached until
        the next table mutation."""
        if self._tables_dirty:
            self._tables_dev = jnp.asarray(self.tables)
            self._tables_prefix_cache = {}
            self._tables_dirty = False
        if prefix is None or prefix >= self.max_blocks:
            return self._tables_dev
        got = self._tables_prefix_cache.get(prefix)
        if got is None:
            got = jnp.asarray(self.tables[:, :prefix])
            self._tables_prefix_cache[prefix] = got
        return got

    # -- invariants (tests) ------------------------------------------------
    def check_invariants(self) -> None:
        counts = np.zeros(self.n_blocks + 1, np.int64)
        for s in range(self.n_slots):
            row = [int(self.tables[s, m])
                   for m in range(int(self.n_mapped[s]))]
            assert 0 not in row, "trash block mapped"
            assert len(row) == len(set(row)), \
                f"slot {s} maps a block twice in its own row"
            for b in row:
                counts[b] += 1
        assert (counts[1:] == self.ref[1:]).all(), \
            "refcount drift: ref[] != table-row mapping counts"
        mapped = {b for b in range(1, self.n_blocks + 1) if self.ref[b] > 0}
        free = set(self._free_set)
        assert mapped.isdisjoint(free), "free block still referenced"
        assert mapped | free == set(range(1, self.n_blocks + 1)), \
            "blocks leaked"
        if self.virtual_blocks == self.n_blocks:
            assert len(free) >= self._reserved_total >= 0, \
                "reservation exceeds free blocks"
        else:
            assert self._reserved_total >= 0
        # the generalized (oversubscription-aware) reservation invariant;
        # reduces to the classic free >= reserved at factor 1
        assert (self.n_physical_in_use + self._reserved_total
                <= self.virtual_blocks), \
            "physical_in_use + reserved exceeds the virtual budget"
        for key, blk in self._prefix_registry.items():
            assert self._registered_key.get(blk) == key, \
                "registry / reverse-map mismatch"
            assert blk in mapped or blk in free  # always true, documents it
        assert len(self._registered_key) == len(self._prefix_registry)
        # host swap tier: bounded, key-disjoint from the device registry,
        # and resident-free — a swapped-out prefix owns NO physical block,
        # so no slot's table row (hence no decode/prefill read) can ever
        # touch one
        assert len(self._swap) <= max(self.swap_blocks, 0), \
            "swap store exceeds its capacity"
        assert not (set(self._swap) & set(self._prefix_registry)), \
            "chain key registered on device AND swapped to host"
        for s in range(self.n_slots):
            if s not in self._in_use:
                assert (self.tables[s] == 0).all(), \
                    f"free slot {s} holds mapped blocks"
        assert len(self._in_use) + len(self._free_slots) == self.n_slots
