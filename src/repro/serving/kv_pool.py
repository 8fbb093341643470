"""Paged KV cache pool for the batched serving path (vLLM-style).

One preallocated device arena holds every request's per-layer KV in
fixed-size pages; a free-list allocator hands pages to requests and a
per-request **slot table** maps logical token positions to physical
slots (page * page_size + in-page slot).  K and V are stored
**pre-RoPE** — the same convention as the item / semantic cache pools —
so a page written from an assembled cache block needs no rewrite, and
decode realigns keys to their request positions with one rotation
(RoPE's group property, §III-C3).

Slot tables are what make **cross-request sharing** possible: a page can
be owned by the `serving.block_store.SharedBlockStore` instead of a
request, and any request may point slot-table entries at the store's
slots at *any* logical alignment (block content never has to land
page-aligned).  Private pages are packed densely: a request's private
slots need not sit at their logical positions.  Allocation stays
page-granular — every page is owned by exactly one of {free list, one
request's `page_tables` entry, the block store} — and `pages_for` keeps
one capacity formula for both the reuse and no-reuse paths so decode
shapes (and therefore decoded tokens) are identical either way.

Insertion is block-granular: `write_plan` walks the assembly plan's
contiguous spans (`core.assembly.plan_spans`) and fuses every cached
block's run into one scatter; the selective engine merges the
recomputed tokens' fresh KV host-side and inserts whole *batches* with
`write_at_batch` — one arena update per batch instead of one per span.

Host-side writes use eager ``.at[].set`` (a full-arena copy per call on
CPU, which is why fusing matters); the decode hot loop instead threads
the arenas through the jitted decode step (`serving.batch_engine`) and
installs the returned buffers, so the new tokens' KV lands in-step (the
arenas are donated on TPU/GPU, making the update in-place; CPU lacks
donation and copies).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig
from repro.core.assembly import RECOMPUTE, AssemblyPlan, plan_spans


class PoolExhausted(RuntimeError):
    """No free pages left — caller should defer admission (backpressure)."""


# Arena scatters are eager XLA ops compiled per *shape*: without
# padding, every distinct row count a batch composition produces
# triggers a fresh ~100ms scatter compile — composition is wall-clock
# sensitive, so steady-state serving would keep recompiling.  Padding
# the fused scatters to row-count buckets caps that at O(log) compiles.
# Pad rows target the scratch page (0, 0) with zero values: duplicates
# in one scatter are only ever these identical zero writes, and the
# scratch page is never read.
WRITE_ROW_BUCKET = 512


def _pad_scatter(pages, slots, k, v):
    t = len(pages)
    t_pad = -(-max(t, 1) // WRITE_ROW_BUCKET) * WRITE_ROW_BUCKET
    if t_pad == t:
        return pages, slots, k, v
    extra = t_pad - t
    pages = np.concatenate([pages, np.zeros(extra, pages.dtype)])
    slots = np.concatenate([slots, np.zeros(extra, slots.dtype)])
    zrow = np.zeros((extra,) + k.shape[1:], k.dtype)
    return pages, slots, np.concatenate([k, zrow]), np.concatenate([v, zrow])


@dataclass(frozen=True)
class PoolStats:
    n_pages: int
    page_size: int
    pages_in_use: int
    n_requests: int
    tokens_resident: int

    @property
    def utilization(self) -> float:
        return self.pages_in_use / max(self.n_pages, 1)

    @property
    def internal_fragmentation(self) -> float:
        """Fraction of allocated slots holding no token."""
        cap = self.pages_in_use * self.page_size
        return 1.0 - self.tokens_resident / max(cap, 1)


@dataclass(frozen=True)
class KVExport:
    """One request's pool state as a self-contained host-side record —
    the page-granular unit of KV migration between workers.

    The slot table is stored page-relatively: private entries carry an
    (index into the exported pages, in-page offset) pair so they can be
    rebound to whatever pages the destination pool hands out;
    store-shared entries (`owner_page == -1`) carry the SOURCE pool's
    physical slot id in `foreign_slots` and must be translated by the
    importer through a source-slot -> destination-slot map (built from
    the destination store's blocks).  `page_k`/`page_v` are the private
    pages' full bytes, (P, page_size, L, Hkv, Dh) pre-RoPE — unused
    slots ride along so the import is one fused scatter and the
    round-trip is bitwise.
    """

    rid: int
    seq_len: int
    page_size: int
    owner_page: np.ndarray     # (n_slots,) exported-page index, -1=shared
    owner_off: np.ndarray      # (n_slots,) in-page offset where owned
    foreign_slots: np.ndarray  # (n_slots,) source slot id where shared
    spare_page: np.ndarray     # (n_spare,) exported-page index
    spare_off: np.ndarray      # (n_spare,)
    page_k: np.ndarray         # (P, page_size, L, Hkv, Dh)
    page_v: np.ndarray

    @property
    def n_pages(self) -> int:
        return self.page_k.shape[0]

    @property
    def nbytes(self) -> int:
        """Private-page payload bytes (the part migration must move)."""
        return self.page_k.nbytes + self.page_v.nbytes


class PagedKVPool:
    """Fixed-page KV arena + free-list allocator + per-request slot tables.

    Arena layout: (n_pages, n_layers, n_kv_heads, page_size, head_dim)
    for K and V separately, dtype float32 (pre-RoPE values).  The
    (page_size, head_dim) plane of one layer's one kv head is trailing,
    so the paged-decode kernel reads it as one tile Mosaic accepts.
    Token rows still go in and out as (t, L, Hkv, Dh):
    ``arena[pages, :, :, slots]`` puts the token axis first.

    ``device`` places the arenas on one device (a cluster worker's
    chip); ``mesh`` shards them instead.
    """

    def __init__(self, n_layers: int, n_kv_heads: int, head_dim: int,
                 page_size: int = 16, n_pages: int = 512,
                 dtype: str = "float32", mesh=None, device=None):
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.n_layers = n_layers
        self.mesh = mesh
        shape = (self.n_pages, n_layers, n_kv_heads, self.page_size, head_dim)
        arena_k = jnp.zeros(shape, jnp.dtype(dtype), device=device)
        arena_v = jnp.zeros(shape, jnp.dtype(dtype), device=device)
        if mesh is not None:
            # per-device arena planes: each device holds every page but
            # only its slice of the kv-head axis (the wk/wv head split).
            # Slot tables and page bookkeeping below stay host-side numpy
            # and device-agnostic; eager `.at[].set` scatters and decode
            # gathers on the placed arenas preserve this sharding, so no
            # write/read path changes
            from repro.sharding.specs import serving_arena_spec

            msz = dict(mesh.shape).get("model", 1)
            if n_kv_heads % msz:
                raise ValueError(
                    f"arena kv-head axis of {n_kv_heads} cannot shard over "
                    f"the mesh model axis of {msz} devices (mesh.tp={msz}): "
                    f"pick a tp dividing n_kv_heads")
            sharding = jax.sharding.NamedSharding(mesh, serving_arena_spec())
            arena_k = jax.device_put(arena_k, sharding)
            arena_v = jax.device_put(arena_v, sharding)
        self.arena_k = arena_k
        self.arena_v = arena_v
        # page 0 is reserved as scratch: padded decode-batch rows write
        # their dummy token there, and padded slot-table entries point at
        # it (reads are masked by seq_lens).  It is never allocated.
        self._free: List[int] = list(range(self.n_pages - 1, 0, -1))
        # private pages owned by each request (page-granular ownership)
        self.page_tables: Dict[int, List[int]] = {}
        # logical position -> physical slot, per request.  Entries may
        # point into private pages *or* store-owned shared pages.
        self.slot_tables: Dict[int, np.ndarray] = {}
        self.seq_lens: Dict[int, int] = {}
        # claimed-but-unassigned private slots, per request: the slack a
        # mapped allocation reserves so mid-prefill remaps (`remap_private`)
        # never have to race other requests for free pages
        self._spare: Dict[int, List[int]] = {}
        self.peak_pages = 0

    # ------------------------------ allocator ------------------------------
    def pages_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.page_size)

    @property
    def bytes_per_token(self) -> int:
        """fp32 K+V bytes one token row occupies across all layers —
        the unit spill-tier capacity and transfer modeling price in."""
        _, n_layers, n_kv_heads, _, head_dim = self.arena_k.shape
        row = n_layers * n_kv_heads * head_dim
        return int(2 * self.arena_k.dtype.itemsize * row)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_admit(self, n_tokens: int) -> bool:
        return len(self._free) >= self.pages_for(n_tokens)

    def page_slots(self, pages: Sequence[int]) -> np.ndarray:
        """Physical slot ids covered by `pages`, in page order."""
        pages = np.asarray(pages, np.int64)
        return (pages[:, None] * self.page_size
                + np.arange(self.page_size)[None, :]).reshape(-1)

    def _bump_peak(self) -> None:
        self.peak_pages = max(self.peak_pages,
                              self.n_pages - 1 - len(self._free))

    def alloc_pages(self, n: int) -> List[int]:
        """Raw page grab with no request bookkeeping — the block store's
        allocation path.  The caller owns the pages until it hands them
        back through `release_pages`."""
        if n > len(self._free):
            raise PoolExhausted(f"need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        self._bump_peak()
        return pages

    def release_pages(self, pages: Sequence[int]) -> None:
        self._free.extend(pages)

    def alloc(self, rid: int, n_tokens: int) -> List[int]:
        """Reserve private pages for `n_tokens` slots; seq_len starts at 0."""
        if rid in self.page_tables:
            raise KeyError(f"request {rid} already allocated")
        need = self.pages_for(n_tokens)
        if need > len(self._free):
            raise PoolExhausted(
                f"need {need} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(need)]
        self.page_tables[rid] = pages
        self.slot_tables[rid] = self.page_slots(pages).astype(np.int64)
        self.seq_lens[rid] = 0
        self._bump_peak()
        return pages

    def alloc_mapped(self, rid: int, n_tokens: int,
                     mapped_positions: np.ndarray,
                     mapped_slots: np.ndarray,
                     extra_pages: int = 0) -> List[int]:
        """Reserve capacity for `n_tokens` slots with some logical
        positions pointing at *shared* physical slots (store-owned pages).

        Capacity is `pages_for(n_tokens) * page_size` slots — the same
        formula as `alloc` — but only the non-mapped slots consume
        private pages, packed densely (the last private page's unused
        slots are fragmentation, bounded by page_size - 1 per request).
        The shared slots are NOT owned by this request: `free` returns
        only the private pages, and the caller is responsible for the
        store-side refcounts.

        ``extra_pages`` claims additional private pages whose slots go
        to the request's spare list — headroom a chunk-resumable prefill
        reserves up front so `remap_private` (un-sharing positions the
        selective pass later decides to recompute) can never hit
        `PoolExhausted` mid-flight.
        """
        if rid in self.page_tables:
            raise KeyError(f"request {rid} already allocated")
        mapped_positions = np.asarray(mapped_positions, np.int64)
        mapped_slots = np.asarray(mapped_slots, np.int64)
        total_slots = self.pages_for(n_tokens) * self.page_size
        n_priv = total_slots - len(mapped_positions)
        need = -(-n_priv // self.page_size) if n_priv > 0 else 0
        need += max(int(extra_pages), 0)
        if need > len(self._free):
            raise PoolExhausted(
                f"need {need} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(need)]
        table = np.full(total_slots, -1, np.int64)
        table[mapped_positions] = mapped_slots
        all_slots = self.page_slots(pages)
        priv = all_slots[:max(n_priv, 0)]
        table[table < 0] = priv
        self.page_tables[rid] = pages
        self.slot_tables[rid] = table
        self._spare[rid] = list(all_slots[max(n_priv, 0):])
        self.seq_lens[rid] = (int(mapped_positions.max()) + 1
                              if len(mapped_positions) else 0)
        self._bump_peak()
        return pages

    def remap_private(self, rid: int, positions: np.ndarray) -> None:
        """Point store-mapped logical `positions` at this request's own
        private slots instead — the mid-prefill incremental append: a
        chunk-resumable prefill maps every store-resident position at
        admission, and un-shares the ones Eq. 3 selection later marks
        for recomputation (their fresh KV must land privately; writing
        through the shared slot would corrupt the store's block).

        Draws from the spare slots reserved at `alloc_mapped` first and
        only then claims new pages, so a request that reserved its
        admission bound as ``extra_pages`` can never fail here."""
        positions = np.asarray(positions, np.int64)
        if len(positions) == 0:
            return
        spare = self._spare.setdefault(rid, [])
        short = len(positions) - len(spare)
        if short > 0:
            n_new = -(-short // self.page_size)
            if n_new > len(self._free):
                raise PoolExhausted(
                    f"remap needs {n_new} pages, {len(self._free)} free")
            pages = [self._free.pop() for _ in range(n_new)]
            self.page_tables[rid].extend(pages)
            spare.extend(self.page_slots(pages))
            self._bump_peak()
        table = self.slot_tables[rid]
        table[positions] = [spare.pop(0) for _ in range(len(positions))]

    def free(self, rid: int) -> None:
        """Release a request's private pages.  Idempotent: freeing an
        unknown (or already-freed) rid is a no-op, so a duplicate
        `finish()` can never crash the batcher loop."""
        pages = self.page_tables.pop(rid, None)
        if pages is None:
            return
        self._free.extend(pages)
        self.slot_tables.pop(rid, None)
        self.seq_lens.pop(rid, None)
        self._spare.pop(rid, None)

    def stats(self) -> PoolStats:
        in_use = sum(len(t) for t in self.page_tables.values())
        return PoolStats(n_pages=self.n_pages, page_size=self.page_size,
                         pages_in_use=in_use,
                         n_requests=len(self.page_tables),
                         tokens_resident=sum(self.seq_lens.values()))

    # ------------------------------- writes --------------------------------
    def _phys(self, rid: int, positions: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Logical token slots -> (page ids, in-page slots), growing the
        slot table by one private page if a position lands past current
        capacity."""
        table = self.slot_tables[rid]
        top = int(positions.max())
        while top >= len(table):
            if not self._free:
                raise PoolExhausted("decode append: no free pages")
            page = self._free.pop()
            self.page_tables[rid].append(page)
            table = np.concatenate([table, self.page_slots([page])])
            self.slot_tables[rid] = table
            self._bump_peak()
        slots = table[positions]
        return ((slots // self.page_size).astype(np.int64),
                (slots % self.page_size).astype(np.int64))

    def write_at(self, rid: int, positions: np.ndarray,
                 k: np.ndarray, v: np.ndarray,
                 layer: Optional[int] = None) -> None:
        """Scatter pre-RoPE (k, v) into logical slots.

        k/v: (t, L, Hkv, Dh), or (t, Hkv, Dh) when `layer` selects a
        single layer plane (e.g. the always-fresh layer-0 KV from the
        selective engine).
        """
        self.write_at_batch([(rid, positions, k, v)], layer=layer)

    def write_at_batch(self, entries: Sequence[tuple],
                       layer: Optional[int] = None,
                       deep: bool = False) -> None:
        """Fused multi-request scatter: ONE arena update for any number
        of requests' writes.

        entries: sequence of (rid, positions, k, v).  Positions must be
        unique within an entry (duplicate physical slots across a single
        scatter have undefined write order under XLA).  Entries with no
        positions are skipped (a fully store-mapped request writes
        nothing).  Arena updates are eager copies on CPU (`.at[].set`),
        so fusing a batch's insertions into one scatter is what makes
        the batched prefill's pool insertion O(1) copies instead of
        O(requests · spans).

        ``deep`` writes only layer planes 1..L-1 from (t, L-1, ...) rows
        — the chunk-resumable prefill's finalize path, whose layer-0
        plane already landed incrementally as chunks completed.
        """
        pages_all, slots_all, ks, vs = [], [], [], []
        for rid, positions, k, v in entries:
            positions = np.asarray(positions, np.int64)
            if len(positions) == 0:
                continue
            pages, slots = self._phys(rid, positions)
            pages_all.append(pages)
            slots_all.append(slots)
            ks.append(np.asarray(k))
            vs.append(np.asarray(v))
            self.seq_lens[rid] = max(self.seq_lens[rid],
                                     int(positions.max()) + 1)
        if not pages_all:
            return
        pages = np.concatenate(pages_all)
        slots = np.concatenate(slots_all)
        k = np.concatenate(ks)
        v = np.concatenate(vs)
        pages, slots, k, v = _pad_scatter(pages, slots, k, v)
        if deep:
            self.arena_k = self.arena_k.at[pages, 1:, :, slots].set(k)
            self.arena_v = self.arena_v.at[pages, 1:, :, slots].set(v)
        elif layer is None:
            self.arena_k = self.arena_k.at[pages, :, :, slots].set(k)
            self.arena_v = self.arena_v.at[pages, :, :, slots].set(v)
        else:
            self.arena_k = self.arena_k.at[pages, layer, :, slots].set(k)
            self.arena_v = self.arena_v.at[pages, layer, :, slots].set(v)

    def write_slots(self, slot_ids: np.ndarray,
                    k: np.ndarray, v: np.ndarray) -> None:
        """Direct physical-slot scatter (no request bookkeeping) — the
        block store's insertion path.  k/v: (t, L, Hkv, Dh)."""
        self.write_slots_batch([(slot_ids, k, v)])

    def write_slots_batch(self, entries: Sequence[tuple]) -> None:
        """Fused multi-block physical-slot scatter: ONE arena update for
        any number of (slot_ids, k, v) writes.  Arena updates are eager
        full copies on CPU, so the store flushes a whole prefill batch's
        block insertions through here instead of paying one copy per
        block."""
        if not entries:
            return
        slot_ids = np.concatenate(
            [np.asarray(s, np.int64) for s, _, _ in entries])
        k = np.concatenate([np.asarray(k) for _, k, _ in entries])
        v = np.concatenate([np.asarray(v) for _, _, v in entries])
        pages = slot_ids // self.page_size
        slots = slot_ids % self.page_size
        pages, slots, k, v = _pad_scatter(pages, slots, k, v)
        self.arena_k = self.arena_k.at[pages, :, :, slots].set(k)
        self.arena_v = self.arena_v.at[pages, :, :, slots].set(v)

    def write_prompt(self, rid: int, k: np.ndarray, v: np.ndarray) -> None:
        """Insert a full prompt cache (n, L, Hkv, Dh) starting at slot 0."""
        self.write_at(rid, np.arange(k.shape[0]), k, v)

    def write_plan(self, rid: int, plan: AssemblyPlan,
                   cached_k: np.ndarray, cached_v: np.ndarray) -> int:
        """Block-granular insertion of an assembly plan's cached spans.

        cached_k/v: (n, L, Hkv, Dh) pre-RoPE as returned by
        `assembly.gather_cached_kv`.  RECOMPUTE spans are skipped (the
        engine scatters fresh KV there after the selective pass).
        -> number of tokens inserted from cache blocks.
        """
        pos_runs = [np.arange(s.start, s.end) for s in plan_spans(plan)
                    if s.source != RECOMPUTE]
        if not pos_runs:
            return 0
        # one fused scatter for all spans (each span is still one
        # contiguous block-granular run; fusing just avoids paying a
        # full-arena copy per span on CPU)
        pos = np.concatenate(pos_runs)
        self.write_at(rid, pos, cached_k[pos], cached_v[pos])
        return len(pos)

    def append_slots(self, rids: Sequence[int]
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Claim the next physical slot for each request's new decode token.

        Grows slot tables across page boundaries and bumps seq_lens; the
        actual KV write happens inside the jitted decode step (which owns
        the arena buffers).  -> (pages (N,), slots (N,)) int32.

        Transactional: if any request's growth hits `PoolExhausted`, every
        mutation this call already made (seq_len bumps, appended pages)
        is rolled back before the exception propagates, so the batcher
        can preempt a request and retry without leaked pages or
        phantom-length sequences.
        """
        pages = np.zeros(len(rids), np.int32)
        slots = np.zeros(len(rids), np.int32)
        done: List[tuple] = []          # (rid, n_pages_appended)
        try:
            for i, rid in enumerate(rids):
                before = len(self.page_tables[rid])
                pos = np.asarray([self.seq_lens[rid]])
                pg, sl = self._phys(rid, pos)
                pages[i], slots[i] = pg[0], sl[0]
                self.seq_lens[rid] += 1
                done.append((rid, len(self.page_tables[rid]) - before))
        except PoolExhausted:
            for rid, n_new in done:
                self.seq_lens[rid] -= 1
                for _ in range(n_new):
                    self._free.append(self.page_tables[rid].pop())
                    self.slot_tables[rid] = \
                        self.slot_tables[rid][:-self.page_size]
            raise
        return pages, slots

    def update_arenas(self, arena_k, arena_v) -> None:
        """Install arenas returned by the (donating) jitted decode step."""
        self.arena_k = arena_k
        self.arena_v = arena_v

    # ------------------------------ migration ------------------------------
    def export_request(self, rid: int) -> "KVExport":
        """Read-only snapshot of one request's pool state for migration.

        Captures the private pages' bytes (host readback), the slot
        table re-expressed page-relatively (private entries become
        (exported-page index, in-page offset) pairs; store-shared
        entries stay as source-pool physical slot ids the importer must
        translate), the spare-slot list and seq_len.  Nothing in the
        source pool is mutated — the caller frees the source side only
        after a successful `import_request` on the destination.
        """
        pages = self.page_tables[rid]
        index = {p: i for i, p in enumerate(pages)}
        table = self.slot_tables[rid]
        t_page = table // self.page_size
        t_off = table % self.page_size
        owner_page = np.asarray(
            [index.get(int(p), -1) for p in t_page], np.int64)
        owner_off = np.where(owner_page >= 0, t_off, 0).astype(np.int64)
        foreign_slots = np.where(owner_page < 0, table, -1).astype(np.int64)
        spare = np.asarray(self._spare.get(rid, []), np.int64)
        spare_page = np.asarray(
            [index[int(s) // self.page_size] for s in spare], np.int64)
        spare_off = (spare % self.page_size if len(spare)
                     else np.zeros(0, np.int64))
        page_idx = np.asarray(pages, np.int64)
        page_k = np.asarray(self.arena_k[page_idx], np.float32)
        page_v = np.asarray(self.arena_v[page_idx], np.float32)
        # host layout (P, page_size, L, Hkv, Dh): slot rows, page-major
        page_k = page_k.transpose(0, 3, 1, 2, 4)
        page_v = page_v.transpose(0, 3, 1, 2, 4)
        return KVExport(rid=rid, seq_len=self.seq_lens[rid],
                        page_size=self.page_size, owner_page=owner_page,
                        owner_off=owner_off, foreign_slots=foreign_slots,
                        spare_page=spare_page, spare_off=spare_off,
                        page_k=page_k, page_v=page_v)

    def import_request(self, export: "KVExport",
                       foreign_slot_map: Optional[Dict[int, int]] = None
                       ) -> List[int]:
        """Materialize an exported request in THIS pool.

        Allocates fresh private pages for every exported page, rewrites
        the slot table against them, lands the page bytes in one fused
        scatter and restores seq_len + spare slots.  Store-shared
        entries are translated through `foreign_slot_map` (source
        physical slot -> destination physical slot, built by the store
        layer from its own blocks).  Transactional: every failure path
        (`PoolExhausted`, an unmapped foreign slot, a duplicate rid) is
        checked before the first mutation, so a failed import leaves the
        destination pool untouched and `check_partition` holds on both
        pools either way.
        """
        rid = export.rid
        if export.page_size != self.page_size:
            raise ValueError(
                f"page_size mismatch: export {export.page_size}, "
                f"pool {self.page_size}")
        if rid in self.page_tables:
            raise KeyError(f"request {rid} already allocated")
        fmap = foreign_slot_map or {}
        foreign = export.foreign_slots[export.owner_page < 0]
        missing = [int(s) for s in foreign if int(s) not in fmap]
        if missing:
            raise KeyError(
                f"import of request {rid}: no destination mapping for "
                f"shared slots {missing[:4]}")
        need = export.n_pages
        if need > len(self._free):
            raise PoolExhausted(
                f"import needs {need} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(need)]
        page_arr = np.asarray(pages, np.int64)
        table = np.empty(len(export.owner_page), np.int64)
        owned = export.owner_page >= 0
        table[owned] = (page_arr[export.owner_page[owned]] * self.page_size
                        + export.owner_off[owned])
        table[~owned] = [fmap[int(s)] for s in export.foreign_slots[~owned]]
        self.page_tables[rid] = pages
        self.slot_tables[rid] = table
        self.seq_lens[rid] = export.seq_len
        self._spare[rid] = list(page_arr[export.spare_page] * self.page_size
                                + export.spare_off)
        if need:
            self.write_slots(self.page_slots(pages),
                             export.page_k.reshape(
                                 (-1,) + export.page_k.shape[2:]),
                             export.page_v.reshape(
                                 (-1,) + export.page_v.shape[2:]))
        self._bump_peak()
        return pages

    # -------------------------------- reads --------------------------------
    def seq_len(self, rid: int) -> int:
        return self.seq_lens[rid]

    def gather(self, rid: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side readback of one request's (k, v): (S, L, Hkv, Dh)."""
        n = self.seq_lens[rid]
        sl = self.slot_tables[rid][:n]
        pages, slots = sl // self.page_size, sl % self.page_size
        k = np.asarray(self.arena_k[pages, :, :, slots])
        v = np.asarray(self.arena_v[pages, :, :, slots])
        return k, v

    def batch_tables(self, rids: Sequence[int], pad_pages_to: int = 4
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Padded slot-table batch for the jitted decode step.

        -> (tables (N, S) int32 physical slot ids, seq_lens (N,) int32).
        S is padded to a multiple of `pad_pages_to * page_size` slots to
        bound jit retraces; pad entries point at slot 0 (the scratch
        page) and are masked by seq_lens.
        """
        chunk = pad_pages_to * self.page_size
        max_s = max(len(self.slot_tables[r]) for r in rids)
        max_s = -(-max_s // chunk) * chunk
        tables = np.zeros((len(rids), max_s), np.int32)
        lens = np.zeros(len(rids), np.int32)
        for i, r in enumerate(rids):
            t = self.slot_tables[r]
            tables[i, :len(t)] = t
            lens[i] = self.seq_lens[r]
        return tables, lens


def page_views(tables: np.ndarray, lens: np.ndarray,
               new_pages: np.ndarray, new_slots: np.ndarray,
               page_size: int, pad_pages_to: int = 4
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Page-granular decode views for the fused paged-attention kernel.

    Slot tables are slot-granular (a row may interleave private and
    store-shared slots at arbitrary alignment, store runs need not be
    page-aligned), so a classic per-request *block table* doesn't exist.
    What does exist: the set of physical pages a row touches, with each
    in-page slot tagged by the logical position it serves.  Attention is
    permutation-invariant over keys, so the kernel can stream pages in
    any order as long as every live slot carries its true position — the
    position drives both the RoPE realignment and the liveness mask.

    tables: (N, S) physical slot ids in logical order (`batch_tables`
    layout, pad entries masked by `lens`); lens: (N,) tokens resident
    before this step (= the new token's logical position);
    new_pages/new_slots: (N,) the physical slot claimed for this step's
    token (`append_slots`) — included in the view at position len, so
    the kernel reads the new token's KV from the arena the decode step
    just wrote, no concat needed.

    -> (page_ids (N, Pmax) int32, slot_pos (N, Pmax, page_size) int32):
    `page_ids[i, j]` is the j-th distinct physical page row i touches
    (first-appearance order); `slot_pos[i, j, t]` is the logical
    position slot t of that page serves for row i, or -1 when it serves
    none (other requests' tokens, pad slots).  Pmax is padded to a
    `pad_pages_to` multiple; pad columns reference the scratch page 0
    with all-(-1) positions.  A pad decode row (len 0, new slot at the
    scratch page) yields exactly one live slot, so its softmax is never
    empty.
    """
    tables = np.asarray(tables)
    n = tables.shape[0]
    lens = np.asarray(lens, np.int64)
    new_slot_ids = (np.asarray(new_pages, np.int64) * page_size
                    + np.asarray(new_slots, np.int64))
    per_row = []
    for i in range(n):
        ln = int(lens[i])
        slots = np.empty(ln + 1, np.int64)
        slots[:ln] = tables[i, :ln]
        slots[ln] = new_slot_ids[i]
        pages = slots // page_size
        offs = slots % page_size
        uniq, first, inv = np.unique(pages, return_index=True,
                                     return_inverse=True)
        order = np.argsort(first, kind="stable")
        rank = np.empty(len(uniq), np.int64)
        rank[order] = np.arange(len(uniq))
        spos = np.full((len(uniq), page_size), -1, np.int32)
        # distinct logical positions live in distinct physical slots, so
        # the (page-rank, offset) pairs are unique — no write collides
        spos[rank[inv], offs] = np.arange(ln + 1)
        per_row.append((uniq[order].astype(np.int32), spos))
    pmax = max(len(p) for p, _ in per_row)
    pmax = max(-(-pmax // pad_pages_to) * pad_pages_to, pad_pages_to)
    page_ids = np.zeros((n, pmax), np.int32)
    slot_pos = np.full((n, pmax, page_size), -1, np.int32)
    for i, (p, sp) in enumerate(per_row):
        page_ids[i, :len(p)] = p
        slot_pos[i, :len(p)] = sp
    return page_ids, slot_pos


def pool_for(cfg: LMConfig, page_size: int = 16, n_pages: int = 512,
             mesh=None, device=None) -> PagedKVPool:
    """Pool sized from a model config (serving launcher convenience).
    With `mesh`, the arenas are sharded over its model axis; with
    `device`, they live on that one device."""
    return PagedKVPool(cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim,
                       page_size=page_size, n_pages=n_pages, mesh=mesh,
                       device=device)
