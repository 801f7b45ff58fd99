"""FarPool: the disaggregated buffer pool (paper §4.4 memory stack).

A paged, device-resident u32/f32 word buffer with:
  * 2 MiB naturally-aligned pages (paper's MMU page size),
  * a host-side page table mapping (table_id, extent) -> pages — the TLB
    analogue (the paper's TLB "holds all mappings"; so does this dict),
  * striped allocation across shards — the paper's multi-channel DRAM
    interleaving, which is what makes vectorized selection (Fig. 8c) and
    smart addressing (Fig. 7) pay off,
  * capacity accounting + quota per client.

The read path is device-resident: `gather_rows` / `gather_columns` are pure
functions of `(buf, pages)` that are safe to call *inside* a jitted
program, so the fused request executable (core/pipeline.py) consumes pages
directly — one compiled program does gather + operators, with no separate
`read_table` dispatch on the hot path.

On a multi-device mesh the page axis is sharded over the pool axis
(`NamedSharding(mesh, P("model"))`), so page p lives on device
p // (n_pages / n_shards); the round-robin-across-chunks allocator below
stripes consecutive table extents across devices, like the paper's MMU
stripes consecutive addresses across DRAM channels.
"""
from __future__ import annotations

import functools
import math
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.table import FTable, WORD_BYTES
from repro.distributed import compress as pagec
from repro.kernels import tier as ktier

PAGE_BYTES = 2 * 1024 * 1024


# ---------------------------------------------------------------- read path
def read_pages(buf: jnp.ndarray, pages: jnp.ndarray) -> jnp.ndarray:
    """Copy pages out of the pool -> pages.shape + (page_words,).

    One dynamic slice per page, in sequence. A row gather `buf[pages]`
    with page-sized slices makes the TPU compiler split the whole pool
    column-wise into temporaries (pool-sized HBM, a minute of compile);
    so does any per-page slice under `vmap`, which is why the stacked
    dispatch calls this on its (B, P) page matrix before vmapping.
    """
    flat = pages.reshape(-1)
    out = jax.lax.map(
        lambda p: jax.lax.dynamic_index_in_dim(buf, p, keepdims=False), flat)
    return out.reshape(pages.shape + buf.shape[1:])


def rows_of(paged: jnp.ndarray, n_rows: int, row_words: int) -> jnp.ndarray:
    """A table's copied pages (P, page_words) -> (n_rows, row_words) f32."""
    flat = paged.reshape(-1)
    return flat[: n_rows * row_words].reshape(n_rows, row_words)


def columns_of(paged: jnp.ndarray, n_rows: int, row_words: int,
               col_idx: tuple[int, ...]) -> jnp.ndarray:
    """Smart addressing (paper §5.2) as strided slices of a table's copied
    pages: only the projected columns' words. Returns column-major
    (k, n_rows) — lane-dense on the TPU, where an (n_rows, k) array would
    be padded to 128 lanes."""
    if n_rows == 0:
        return jnp.zeros((len(col_idx), 0), paged.dtype)
    flat = paged.reshape(-1)
    end = (n_rows - 1) * row_words + 1
    return jnp.stack([jax.lax.slice(flat, (c,), (c + end,), (row_words,))
                      for c in col_idx])


def gather_rows(buf: jnp.ndarray, pages: jnp.ndarray, n_rows: int,
                row_words: int) -> jnp.ndarray:
    """Device-resident page gather -> (n_rows, row_words) f32.

    Pure in (buf, pages); n_rows/row_words are static shapes. Safe inside a
    traced program — the fused pipeline executable calls this directly so
    the pool read is part of the same compiled dispatch.
    """
    return rows_of(read_pages(buf, pages), n_rows, row_words)


def gather_columns(buf: jnp.ndarray, pages: jnp.ndarray, n_rows: int,
                   row_words: int, col_idx: tuple[int, ...]) -> jnp.ndarray:
    """`columns_of` straight from the pool, as rows: (n_rows, k)."""
    return columns_of(read_pages(buf, pages), n_rows, row_words, col_idx).T


@jax.jit
def _write_pages(buf, pages, data):
    """The pool with data (P, page_words) written over its pages: one
    dynamic update per page, the write-side twin of `read_pages`."""
    return jax.lax.fori_loop(
        0, pages.shape[0],
        lambda i, b: jax.lax.dynamic_update_index_in_dim(b, data[i],
                                                         pages[i], 0),
        buf)


@functools.partial(jax.jit, static_argnames=("n_rows", "row_words"))
def _gather_rows_jit(buf, pages, *, n_rows, row_words):
    return gather_rows(buf, pages, n_rows, row_words)


@functools.partial(jax.jit, static_argnames=("n_rows", "row_words", "col_idx"))
def _gather_columns_jit(buf, pages, *, n_rows, row_words, col_idx):
    return gather_columns(buf, pages, n_rows, row_words, col_idx)


@functools.partial(jax.jit,
                   static_argnames=("n_rows", "row_words", "page_words"))
def _gather_rows_tiered_jit(buf, tier, *, n_rows, row_words, page_words):
    return ktier.gather_rows_tiered(buf, tier, n_rows, row_words, page_words)


@functools.partial(jax.jit, static_argnames=("n_rows", "row_words", "col_idx",
                                             "page_words"))
def _gather_columns_tiered_jit(buf, tier, *, n_rows, row_words, col_idx,
                               page_words):
    return ktier.gather_columns_tiered(buf, tier, n_rows, row_words, col_idx,
                                       page_words)


@dataclass
class TableTier:
    """Per-table tiering state: the per-page tier bit plus the decode
    descriptors the fused pipeline consumes (kernels/tier.py layout).

    `phys` tracks where each LOGICAL page lives NOW — its original raw
    page while hot, or the shared cold frame holding its compressed
    stream after demotion (`FTable.pages` keeps the logical view; every
    pool read/write path consults this entry first). Word tables demote
    page-granular through the bit-packed plane codec; string tables
    demote extent-granular through the block codec (`blob_*`) because
    their dispatch path reads the byte sideband, not pool words."""
    C: int                        # codec plane count == row_words
    is_str: bool
    n_words: np.ndarray           # (P,)  logical words per page
    cold: np.ndarray              # (P,)  bool — THE per-page tier bit
    phys: np.ndarray              # (P,)  int32 raw page | cold frame
    mode: np.ndarray              # (P,C) int32 plane modes (RAW rows = hot)
    width: np.ndarray             # (P,C) int32 packed bits per value
    base: np.ndarray              # (P,C) uint32 delta bases
    dictoff: np.ndarray           # (P,C) int32 FRAME-relative dict words
    bitoff: np.ndarray            # (P,C) int32 FRAME-relative plane bits
    counts: np.ndarray            # (P,C) int64 values per plane
    dictlen: np.ndarray           # (P,C) int32 dict words per plane
    span: np.ndarray              # (P,2) int32 (word off, words) in frame
    crc: np.ndarray               # (P,)  uint32 page codec CRC
    frames: dict[int, set[int]] = field(default_factory=dict)
    hits: deque = field(default_factory=deque)   # promotion hysteresis
    blob: tuple[int, ...] = ()    # str extent: frames holding block stream
    blob_len: int = 0             # str extent: encoded byte length

    @classmethod
    def fresh(cls, ft: FTable, page_words: int) -> "TableTier":
        P = len(ft.pages)
        C = ft.row_words
        n_words = np.minimum(
            page_words,
            np.maximum(0, ft.n_words - np.arange(P, dtype=np.int64)
                       * page_words)).astype(np.int64)
        k = np.arange(ft.n_words, dtype=np.int64)
        counts = np.zeros((P, C), np.int64)
        np.add.at(counts, (k // page_words, k % C), 1)
        return cls(C=C, is_str=bool(ft.str_width), n_words=n_words,
                   cold=np.zeros((P,), bool),
                   phys=np.asarray(ft.pages, np.int32),
                   mode=np.full((P, C), pagec.MODE_RAW, np.int32),
                   width=np.ones((P, C), np.int32),
                   base=np.zeros((P, C), np.uint32),
                   dictoff=np.zeros((P, C), np.int32),
                   bitoff=np.zeros((P, C), np.int32),
                   counts=counts,
                   dictlen=np.zeros((P, C), np.int32),
                   span=np.zeros((P, 2), np.int32),
                   crc=np.zeros((P,), np.uint32))


@dataclass
class PoolStats:
    bytes_read: int = 0
    bytes_written: int = 0
    bytes_shipped: int = 0          # over-the-network response bytes
    requests: int = 0

    @classmethod
    def aggregate(cls, stats: "list[PoolStats]") -> "PoolStats":
        """Cluster-wide roll-up of per-node pool counters."""
        out = cls()
        for s in stats:
            out.bytes_read += s.bytes_read
            out.bytes_written += s.bytes_written
            out.bytes_shipped += s.bytes_shipped
            out.requests += s.requests
        return out


class FarPool:
    """Disaggregated memory node: paged word buffer + page table."""

    def __init__(self, capacity_bytes: int, *, page_bytes: int = PAGE_BYTES,
                 n_shards: int = 1, sharding: jax.sharding.Sharding | None = None,
                 promote_after: int = 3, promote_window: float = 60.0):
        if capacity_bytes % page_bytes:
            raise ValueError("capacity must be page-aligned")
        self.page_bytes = page_bytes
        self.page_words = page_bytes // WORD_BYTES
        self.n_pages = capacity_bytes // page_bytes
        if self.n_pages % n_shards:
            raise ValueError("pages must divide shards")
        self.n_shards = n_shards
        self.chunk = self.n_pages // n_shards     # pages per shard
        # pinned all-zeros pages past the allocatable range: the scheduler
        # pads bucketed page lists with `null_page` so different-sized
        # tables can share a stacked executable (tail rows read zeros and
        # are masked by n_valid). Never allocated, never written. n_shards
        # extra pages keep the page axis divisible by the shard count for
        # device_put with a page-axis sharding; note the pad rows sit at
        # the buffer tail, so under a real multi-shard sharding each
        # device boundary shifts by up to n_shards-1 pages relative to
        # the allocator's p // chunk map (no sharded multi-shard caller
        # exists yet; revisit placement before wiring one up).
        self.null_page = self.n_pages
        buf = jnp.zeros((self.n_pages + n_shards, self.page_words),
                        jnp.float32)
        if sharding is not None:
            buf = jax.device_put(buf, sharding)
        self.buf = buf
        # free lists per shard chunk — striping allocates round-robin chunks.
        # deques: alloc pops left, free appends right — O(1) either end
        # (a plain list.pop(0) is O(n) and quadratic over an alloc storm).
        self._free: list[deque[int]] = [
            deque(range(s * self.chunk, (s + 1) * self.chunk))
            for s in range(n_shards)]
        self._next_table_id = 0
        self.page_table: dict[int, tuple[int, ...]] = {}  # the "TLB"
        self.stats = PoolStats()
        # ----- memory tiering (docs/tiering.md) -----------------------------
        # promotion hysteresis: a cold table promotes after `promote_after`
        # accesses inside a `promote_window`-second window, so a single
        # cold scan runs fused-decompressed instead of thrashing the tier
        # bit, while genuinely re-hot tables come back raw.
        self.promote_after = promote_after
        self.promote_window = promote_window
        self._tier: dict[int, TableTier] = {}     # table_id -> tier entry
        self._tier_dev: dict[int, tuple] = {}     # device descriptor cache
        self._logical: dict[int, int] = {}        # table_id -> logical bytes
        self.tier_stats = {"demoted_pages": 0, "promoted_pages": 0,
                           "incompressible_pages": 0}

    # ------------------------------------------------------------------ mgmt
    @property
    def free_pages(self) -> int:
        return sum(len(f) for f in self._free)

    def alloc_table(self, ft: FTable) -> FTable:
        n_pages = max(1, math.ceil(ft.n_bytes / self.page_bytes))
        if n_pages > self.free_pages:
            raise MemoryError(
                f"pool exhausted: need {n_pages} pages, have {self.free_pages}")
        # round-robin striping across shards, skipping exhausted shards
        # (shard-exhaustion fallback: remaining shards keep serving).
        pages: list[int] = []
        s = 0
        while len(pages) < n_pages:
            free = self._free[s % self.n_shards]
            if free:
                pages.append(free.popleft())
            s += 1
        ft.table_id = self._next_table_id
        self._next_table_id += 1
        ft.pages = tuple(pages)
        self.page_table[ft.table_id] = ft.pages
        self._logical[ft.table_id] = ft.n_bytes
        return ft

    def free_table(self, ft: FTable) -> None:
        te = self._tier.pop(ft.table_id, None)
        self._tier_dev.pop(ft.table_id, None)
        self._logical.pop(ft.table_id, None)
        self.page_table.pop(ft.table_id, None)
        if te is None:
            pages = ft.pages
        else:
            # cold pages' original raw frames were freed at demotion: give
            # back the shared cold frames + the still-hot pages' raw frames
            pages = list(te.frames) + list(te.blob) + [
                int(te.phys[p]) for p in range(len(te.cold))
                if not te.cold[p]]
        for p in pages:
            self._free[p // self.chunk].append(p)
        ft.pages = ()
        ft.table_id = -1

    # ------------------------------------------------------------------- I/O
    def write_table(self, ft: FTable, words: np.ndarray) -> None:
        """words: (n_rows, row_words) f32 (or bitcast-compatible)."""
        if ft.table_id in self._tier:
            # writes land on raw pages only: promote first (a written table
            # is hot by definition; the heat ledger will re-demote later)
            self.promote_table(ft)
        n_pages = len(ft.pages)
        if isinstance(words, np.ndarray):
            # lay host rows out as pages on the host: an (n, A) device
            # array with A < 128 would be padded to 128 lanes on the TPU
            flat = words.astype(np.float32, copy=False).reshape(-1)
            data = np.zeros((n_pages, self.page_words), np.float32)
            data.reshape(-1)[:flat.size] = flat
        else:
            flat = jnp.asarray(words, jnp.float32).reshape(-1)
            data = jnp.pad(flat, (0, n_pages * self.page_words - flat.size)
                           ).reshape(n_pages, self.page_words)
        self.buf = _write_pages(self.buf, jnp.asarray(ft.pages, jnp.int32),
                                jnp.asarray(data))
        self.stats.bytes_written += int(flat.size) * WORD_BYTES

    def gather_rows(self, pages, n_rows: int, row_words: int) -> jnp.ndarray:
        """Device-resident read path (no accounting): one jitted gather."""
        return _gather_rows_jit(self.buf, jnp.asarray(pages, jnp.int32),
                                n_rows=n_rows, row_words=row_words)

    def read_table(self, ft: FTable) -> jnp.ndarray:
        """Full-table RDMA read -> (n_rows, row_words) f32.

        A tiered table decodes in the SAME dispatch (word pages) or via
        the host block codec (string extents) — byte-identical to the raw
        read — and bills the PHYSICAL bytes actually pulled from DRAM
        (compressed for cold pages), per the tiering accounting contract."""
        te = self._tier.get(ft.table_id)
        if te is None:
            rows = self.gather_rows(ft.pages, ft.n_rows, ft.row_words)
            self.stats.bytes_read += ft.n_bytes
            return rows
        self.stats.bytes_read += self.tier_read_bytes(ft)
        if te.is_str:
            return jnp.asarray(self._str_extent_words(ft, te).reshape(
                ft.n_rows, ft.row_words).view(np.float32))
        return _gather_rows_tiered_jit(
            self.buf, self.tier_desc(ft), n_rows=ft.n_rows,
            row_words=ft.row_words, page_words=self.page_words)

    def read_rows(self, ft: FTable, row_idx) -> jnp.ndarray:
        """Row-subset read -> (len(row_idx), row_words) f32.

        Gathers only the selected rows' words through the page table
        (page-indirect addressing, same mechanism as `gather_columns`), so
        a partition-migration step that moves K rows off a node reads K
        rows' worth of DRAM — not the whole extent. `row_idx` are LOCAL
        row positions within this table. Bills exactly the subset."""
        if ft.table_id in self._tier:
            # migration copies read row subsets then usually free the
            # source — promote rather than teach the subset path to decode
            self.promote_table(ft)
        row_idx = np.asarray(row_idx, np.int64)
        if row_idx.size == 0:
            return jnp.zeros((0, ft.row_words), jnp.float32)
        pages = np.asarray(ft.pages, np.int64)
        w = (row_idx[:, None] * ft.row_words
             + np.arange(ft.row_words, dtype=np.int64)[None, :])
        vals = self.buf[jnp.asarray(pages[w // self.page_words], jnp.int32),
                        jnp.asarray(w % self.page_words, jnp.int32)]
        self.stats.bytes_read += int(row_idx.size) * ft.row_words * WORD_BYTES
        return vals

    def read_columns(self, ft: FTable, col_idx: list[int]) -> jnp.ndarray:
        """Smart addressing (paper §5.2): per-column strided reads so only
        the projected columns' words leave DRAM. Returns (n_rows, k).

        On a tiered table only the projected columns' PLANES are unpacked
        (cold) or strided (hot); billing follows the physical bytes."""
        te = self._tier.get(ft.table_id)
        if te is not None and not te.is_str:
            out = _gather_columns_tiered_jit(
                self.buf, self.tier_desc(ft), n_rows=ft.n_rows,
                row_words=ft.row_words, col_idx=tuple(col_idx),
                page_words=self.page_words)
            self.stats.bytes_read += self.tier_read_bytes(ft, col_idx)
            return out
        out = _gather_columns_jit(self.buf, jnp.asarray(ft.pages, jnp.int32),
                                  n_rows=ft.n_rows, row_words=ft.row_words,
                                  col_idx=tuple(col_idx))
        self.stats.bytes_read += ft.n_rows * len(col_idx) * WORD_BYTES
        return out

    def local_rows(self, ft: FTable, shard: int) -> jnp.ndarray:
        """Rows whose pages live on `shard` (for near-data offload)."""
        if ft.table_id in self._tier:
            self.promote_table(ft)      # near-data offload wants raw pages
        own = [p for p in ft.pages if p // self.chunk == shard]
        if not own:
            return jnp.zeros((0, ft.row_words), jnp.float32)
        flat = read_pages(self.buf, jnp.asarray(own, jnp.int32)).reshape(-1)
        return flat.reshape(-1, ft.row_words)

    # -------------------------------------------------- tiering (hot / cold)
    def is_tiered(self, ft: FTable) -> bool:
        """True while any of the table's pages are cold (an entry exists).
        A fully re-promoted table drops its entry and is indistinguishable
        from one that was never demoted."""
        return ft.table_id in self._tier

    def tier_bits(self, ft: FTable) -> np.ndarray:
        """The per-page tier bit: (P,) bool, True = cold (compressed)."""
        te = self._tier.get(ft.table_id)
        if te is None:
            return np.zeros((len(ft.pages),), bool)
        return te.cold.copy()

    def _alloc_frame(self) -> int:
        for free in self._free:
            if free:
                return free.popleft()
        raise MemoryError("pool exhausted: no free frame for tiering")

    def _page_words_u32(self, page: int, n: int) -> np.ndarray:
        # farlint: ok host-sync -- demote/promote are background paths
        return np.asarray(self.buf[page])[:n].view(np.uint32)

    def _write_frame_words(self, frame: int, off: int,
                           words_u32: np.ndarray) -> None:
        self.buf = self.buf.at[frame, off:off + words_u32.size].set(
            jnp.asarray(words_u32.view(np.float32)))

    def demote_table(self, ft: FTable, page_idx=None) -> int:
        """Compress pages of `ft` in place (cold tier). Returns the number
        of pages demoted; each one's raw frame goes back to the free list
        (net capacity gain = raw pages freed - cold frames allocated).
        Incompressible pages keep their raw frame and a raw tier bit.
        String tables demote extent-granular through the block codec."""
        if ft.table_id < 0:
            raise ValueError(f"table {ft.name!r} is not allocated")
        if ft.str_width:
            return self._demote_str(ft)
        te = self._tier.get(ft.table_id)
        if te is None:
            te = TableTier.fresh(ft, self.page_words)
        targets = (range(len(te.cold)) if page_idx is None else page_idx)
        plans: list[tuple[int, pagec.PagePlan]] = []
        for p in targets:
            if te.cold[p]:
                continue
            words = self._page_words_u32(int(te.phys[p]), int(te.n_words[p]))
            plan = pagec.encode_word_page(
                words, te.C, phase=(p * self.page_words) % te.C,
                page_words=self.page_words)
            if plan is None:
                self.tier_stats["incompressible_pages"] += 1
                continue                    # tier bit stays raw, loudly so
            plans.append((p, plan))

        frame, off = -1, self.page_words    # force a fresh frame first
        demoted = 0
        for p, plan in plans:
            m = plan.stream_words
            if off + m > self.page_words:
                if self.free_pages == 0:
                    break                   # partial demotion: no room left
                frame, off = self._alloc_frame(), 0
                te.frames[frame] = set()
            self._write_frame_words(frame, off, plan.stream)
            te.phys[p] = frame
            te.mode[p] = plan.modes
            te.width[p] = plan.widths
            te.base[p] = plan.base
            te.dictoff[p] = np.where(plan.dictoff >= 0,
                                     plan.dictoff + off, 0)
            te.bitoff[p] = plan.bitoff + off * 32
            te.dictlen[p] = plan.dictlen
            te.span[p] = (off, m)
            te.crc[p] = np.uint32(plan.crc)
            te.cold[p] = True
            te.frames[frame].add(p)
            off += m
            # the page's raw frame is free the moment its stream is placed
            raw = int(ft.pages[p])
            self._free[raw // self.chunk].append(raw)
            demoted += 1
        if te.cold.any():
            self._tier[ft.table_id] = te
            self._tier_dev.pop(ft.table_id, None)
        self.tier_stats["demoted_pages"] += demoted
        return demoted

    def promote_table(self, ft: FTable, page_idx=None) -> int:
        """Decompress cold pages back to raw frames (CRC-verified host
        decode; raises `PageCodecError` on corruption instead of restoring
        wrong bytes). A fully-hot table drops its tier entry and
        `ft.pages`/the page table reflect the new raw placement."""
        te = self._tier.get(ft.table_id)
        if te is None:
            return 0
        if te.is_str:
            return self._promote_str(ft)
        targets = (range(len(te.cold)) if page_idx is None else page_idx)
        promoted = 0
        for p in targets:
            if not te.cold[p]:
                continue
            off, m = int(te.span[p, 0]), int(te.span[p, 1])
            frame = int(te.phys[p])
            stream = self._page_words_u32(frame, off + m)[off:].copy()
            plan = pagec.PagePlan(
                n_words=int(te.n_words[p]),
                phase=(p * self.page_words) % te.C,
                modes=te.mode[p].copy(), widths=te.width[p].copy(),
                base=te.base[p].copy(),
                dictoff=np.where(te.dictlen[p] > 0,
                                 te.dictoff[p] - off, -1).astype(np.int32),
                bitoff=(te.bitoff[p] - off * 32).astype(np.int32),
                dictlen=te.dictlen[p].copy(), stream=stream,
                crc=int(te.crc[p]))
            words = pagec.decode_word_page(plan, te.C)
            raw = self._alloc_frame()
            padded = np.zeros((self.page_words,), np.uint32)
            padded[:words.size] = words
            self._write_frame_words(raw, 0, padded)
            te.frames[frame].discard(p)
            if not te.frames[frame]:        # last resident left: frame free
                del te.frames[frame]
                self._free[frame // self.chunk].append(frame)
            te.phys[p] = raw
            te.cold[p] = False
            te.mode[p] = pagec.MODE_RAW
            te.width[p] = 1
            te.base[p] = 0
            te.dictoff[p] = 0
            te.bitoff[p] = 0
            te.dictlen[p] = 0
            promoted += 1
        ft.pages = tuple(int(x) for x in te.phys)
        self.page_table[ft.table_id] = ft.pages
        if not te.cold.any():
            del self._tier[ft.table_id]     # fully hot: transparent again
        self._tier_dev.pop(ft.table_id, None)
        self.tier_stats["promoted_pages"] += promoted
        return promoted

    def _demote_str(self, ft: FTable) -> int:
        te = self._tier.get(ft.table_id)
        if te is not None:
            return 0                        # already cold (all-or-nothing)
        te = TableTier.fresh(ft, self.page_words)
        raw = b"".join(
            self._page_words_u32(int(p), int(te.n_words[i])).tobytes()
            for i, p in enumerate(ft.pages))
        enc = pagec.encode_blocks(raw)
        enc_words = (len(enc) + WORD_BYTES - 1) // WORD_BYTES
        k = max(1, math.ceil(enc_words / self.page_words))
        if k >= len(ft.pages):
            self.tier_stats["incompressible_pages"] += len(ft.pages)
            return 0                        # no capacity win: stay raw
        frames = [self._alloc_frame() for _ in range(k)]
        padded = np.zeros((k * self.page_words,), np.uint32)
        padded[:enc_words] = np.frombuffer(
            enc.ljust(enc_words * WORD_BYTES, b"\0"), np.uint32)
        for i, f in enumerate(frames):
            self._write_frame_words(
                f, 0, padded[i * self.page_words:(i + 1) * self.page_words])
        for p in ft.pages:
            self._free[int(p) // self.chunk].append(int(p))
        te.cold[:] = True
        te.phys[:] = -1
        te.blob = tuple(frames)
        te.blob_len = len(enc)
        self._tier[ft.table_id] = te
        self.tier_stats["demoted_pages"] += len(ft.pages)
        return len(ft.pages)

    def _promote_str(self, ft: FTable) -> int:
        te = self._tier.pop(ft.table_id)
        self._tier_dev.pop(ft.table_id, None)
        words = self._str_extent_words(ft, te)
        pages = [self._alloc_frame() for _ in range(len(te.cold))]
        for i, p in enumerate(pages):
            chunk = words[i * self.page_words:(i + 1) * self.page_words]
            padded = np.zeros((self.page_words,), np.uint32)
            padded[:chunk.size] = chunk
            self._write_frame_words(p, 0, padded)
        for f in te.blob:
            self._free[f // self.chunk].append(f)
        ft.pages = tuple(pages)
        self.page_table[ft.table_id] = ft.pages
        self.tier_stats["promoted_pages"] += len(pages)
        return len(pages)

    def _str_extent_words(self, ft: FTable, te: TableTier) -> np.ndarray:
        """Decode a cold string extent's block stream -> logical u32 words
        (CRC-verified; typed `PageCodecError` on corruption)."""
        enc = b"".join(self._page_words_u32(f, self.page_words).tobytes()
                       for f in te.blob)[:te.blob_len]
        raw = pagec.decode_blocks(enc)
        out = np.zeros((ft.n_words,), np.uint32)
        got = np.frombuffer(raw, np.uint32)
        out[:got.size] = got
        return out

    def note_access(self, ft: FTable) -> bool:
        """Record a request touching `ft`; promote when the hysteresis
        threshold trips (`promote_after` hits within `promote_window`
        seconds). String extents promote on FIRST access — their dispatch
        path needs raw pages, so staying cold has no fused-decode discount.
        Returns True when the access triggered a promotion."""
        te = self._tier.get(ft.table_id)
        if te is None:
            return False
        if te.is_str:
            self._promote_str(ft)
            return True
        now = time.monotonic()
        te.hits.append(now)
        while te.hits and te.hits[0] < now - self.promote_window:
            te.hits.popleft()
        if len(te.hits) >= self.promote_after:
            self.promote_table(ft)
            return True
        return False

    def tier_desc(self, ft: FTable) -> tuple:
        """The table's decode descriptors as device operands (the tuple
        kernels/tier.py consumes), cached per table until the next
        demote/promote flips them."""
        cached = self._tier_dev.get(ft.table_id)
        if cached is not None:
            return cached
        te = self._tier.get(ft.table_id)
        if te is None or te.is_str:
            raise ValueError(f"table {ft.name!r} has no word-tier entry")
        desc = (jnp.asarray(te.phys, jnp.int32),
                jnp.asarray(te.mode, jnp.int32),
                jnp.asarray(te.width, jnp.int32),
                jnp.asarray(te.base, jnp.uint32),
                jnp.asarray(te.dictoff, jnp.int32),
                jnp.asarray(te.bitoff, jnp.int32))
        self._tier_dev[ft.table_id] = desc
        return desc

    def tier_desc_padded(self, ft: FTable, n_pages: int) -> tuple:
        """Host-side descriptor tuple padded to `n_pages` rows with the
        null descriptor (mode RAW + the pinned null page): what a batched
        scheduling round stacks so different-sized tiered tables share one
        bucket executable — padding pages read zeros, exactly like the
        flat path's null-page padding."""
        te = self._tier.get(ft.table_id)
        if te is None or te.is_str:
            raise ValueError(f"table {ft.name!r} has no word-tier entry")
        out = ktier.null_descriptor(n_pages, te.C, self.null_page)
        P = len(te.cold)
        src = (te.phys, te.mode, te.width, te.base, te.dictoff, te.bitoff)
        for dst, s in zip(out, src):
            dst[:P] = s
        return out

    def tier_read_bytes(self, ft: FTable, col_idx=None) -> int:
        """PHYSICAL bytes a full read of `ft` (optionally only `col_idx`
        columns) pulls from DRAM: raw pages bill their logical words, cold
        pages their packed plane words + dictionaries — the 'compressed
        bytes on the wire' half of the tiering accounting contract."""
        te = self._tier.get(ft.table_id)
        if te is None:
            if col_idx is None:
                return ft.n_bytes
            return ft.n_rows * len(col_idx) * WORD_BYTES
        if te.is_str:
            blob_words = (te.blob_len + WORD_BYTES - 1) // WORD_BYTES
            return blob_words * WORD_BYTES
        cols = (np.arange(te.C) if col_idx is None
                else np.asarray(col_idx, np.int64))
        total = 0
        for p in range(len(te.cold)):
            if te.cold[p]:
                if col_idx is None:
                    total += int(te.span[p, 1])
                else:
                    bits = te.counts[p, cols] * te.width[p, cols]
                    total += int(np.sum((bits + 31) // 32
                                        + te.dictlen[p, cols]))
            else:
                total += int(np.sum(te.counts[p, cols]))
        return total * WORD_BYTES

    def tier_summary(self) -> dict:
        """Capacity accounting for the hierarchy: resident logical bytes
        vs the physical frames holding them, plus the effective-capacity
        multiplier the benchmark guards (logical bytes the pool serves per
        byte of DRAM it actually occupies)."""
        logical = sum(self._logical.values())
        used_pages = self.n_pages - self.free_pages
        physical = used_pages * self.page_bytes
        cold_pages = sum(int(te.cold.sum()) for te in self._tier.values())
        return dict(self.tier_stats, cold_pages=cold_pages,
                    logical_bytes=logical, physical_bytes=physical,
                    effective_capacity=(logical / physical
                                        if physical else 0.0))
