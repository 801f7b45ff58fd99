"""Pipeline compiler: operator IR -> ONE fused jitted executable (paper §5.1).

`compile_pipeline(schema, pipeline)` returns a `CompiledPipeline` whose
whole request path — pool-page gather, pre-decrypt, join probe, fused
select/project/pack, group-aggregate, post-encrypt, and response byte
accounting — is a single traced program per (layout, signature): the
analogue of Farview's one RDMA verb triggering the full bump-in-the-wire
pipeline with no CPU round-trips mid-stream.

Entry points:

  pipe(rows[, lengths][, build])                  rows already materialized
  pipe.run_pages(buf, pages, n_valid[, build])    fused gather: the
      executable consumes pool pages directly (FarPool.gather_rows read
      path); `n_valid` is a *traced* scalar masking the tail.
  pipe.run_pages_batched(buf, pages, n_valid[, build])   stacked
      multi-client dispatch: pages (B, P), n_valid (B,) — one vmapped
      executable per scheduling round, results split per client. Page
      lists may be bucket-padded with the pool null page (n_valid masks
      each request's tail); a shared join build table is broadcast.
  pipe.run_strings_batched(strings, lengths, n_valid)    stacked string /
      regex dispatch over a (B, n, w) byte tensor with per-request
      lengths — the DFA/crypt body vmapped over the round's clients.

Every entry point takes an optional `row_ids` operand (traced, one
original-table row index per local row) for cluster partition dispatch:
a pre-Crypt addresses its CTR keystream by those ORIGINAL offsets (a node
holding a row subset of one encrypted table decrypts exactly), and
rows-kind results thread the ids through the packing, returning survivors'
ids as `PipelineResult.sel_ids` — what the client-side scatter-gather
merge sorts on to restore single-node row order byte-identically.

All entry points return a lazy `PipelineResult`: device arrays plus traced
count/byte scalars. `PipelineResult.finalize()` is the ONLY sync point —
it materializes Python-int counts, extracts group-overflow rows, and fires
accounting callbacks. Benchmarks call it inside the timed closure; the
dispatch itself never blocks.

Operator lowering is backend-aware: on TPU the Pallas kernels run inside
the trace (their pad/layout glue becomes part of the traced program), over
a word table held column-major, (A, n): an (n, A) array with A < 128
would be padded to 128 lanes in HBM. Off TPU — where Pallas would run in
interpret mode, emulating the MXU datapath at ~50x cost — the same
operators lower to the XLA-native `*_xla`/ref implementations over (n, A)
rows. `interpret=False` forces the kernel lowering anywhere (its kernels
then run in interpret mode off the TPU); tests hold the two lowerings
byte-identical.

Compiled executables are cached by (schema layout, pipeline signature) —
the analogue of Farview's precompiled partial bitstreams: "reconfiguring a
dynamic region" is a cache lookup + dispatch, and like the paper's
ms-scale swap it never disturbs other clients' pipelines. A repeated
signature at the same shape performs zero retraces (`CompiledPipeline
.traces` counts them; tests/test_fused_path.py regression-checks it).
"""
from __future__ import annotations

import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import operators as op_ir
from repro.core import pool as fpool
from repro.core.regex import compile_regex
from repro.core.table import FTable, WORD_BYTES
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels import tier as ktier

_DROP_KEY = int(kref.KEY_SENTINEL) + 1   # masked-row group key (never in data)


class PipelineResult:
    """Lazy response handle: device arrays + traced count/byte scalars.

    `finalize()` is the only synchronization point — it converts traced
    scalars to Python ints, extracts the group-overflow collision buffer
    (the paper's client-side software merge input), and fires accounting
    callbacks (QPair / pool byte counters). Scalar properties
    (`count`, `groups`, `shipped_bytes`) finalize on first access so
    existing callers keep working; `rows` / `mask` hand back the raw device
    arrays without forcing a sync.
    """

    def __init__(self, kind: str, *, rows=None, count=None, groups=None,
                 mask=None, scalars=None, shipped_bytes=0, read_bytes=0,
                 sel_ids=None, _raw: dict | None = None,
                 _meta: dict | None = None):
        self.kind = kind        # "rows" | "groups" | "mask" | "scalars"
        self.read_bytes = read_bytes    # static: bytes pulled from pool DRAM
        self._rows = rows
        self._count = count
        self._groups = groups
        self._mask = mask
        self._scalars = scalars
        self._shipped = shipped_bytes
        self._ids = sel_ids             # survivors' original row ids, or None
        self._raw = _raw                # unfinalized executable payload
        self._meta = _meta or {}
        self._callbacks: list[Callable] = []

    # ------------------------------------------------------- raw device views
    @property
    def rows(self):
        if self._raw is not None and "rows" in self._raw:
            if not self._meta.get("cm"):
                return self._raw["rows"]
            self.finalize()     # column-major payload: rows exist on host
        return self._rows

    @property
    def mask(self):
        if self._raw is not None and "mask" in self._raw:
            return self._raw["mask"]
        return self._mask

    # ----------------------------------------------------- sync-on-first-read
    @property
    def count(self):
        self.finalize()
        return self._count

    @property
    def groups(self):
        self.finalize()
        return self._groups

    @property
    def scalars(self):
        """An Aggregate's answer: {"count": selected rows, "sums": one
        exact int per term}."""
        self.finalize()
        return self._scalars

    @property
    def shipped_bytes(self):
        self.finalize()
        return self._shipped

    @property
    def sel_ids(self):
        """Survivors' original row ids (np.int64, len == count) when the
        request was dispatched with explicit `row_ids` (cluster partitions);
        None otherwise. The client-side scatter-gather merge sorts on these
        to restore the single-node row order byte-identically."""
        self.finalize()
        return self._ids

    def on_finalize(self, cb: Callable) -> None:
        """Run `cb(self)` once the response is materialized (accounting)."""
        if self._raw is None:
            cb(self)
        else:
            self._callbacks.append(cb)

    def finalize(self) -> "PipelineResult":
        """Materialize the response — the request path's only sync point.

        Converts the traced count/shipped scalars to Python ints, slices
        the survivor-id column (`sel_ids`) and the packed group-overflow
        collision rows out of the raw executable payload, and fires the
        deferred accounting callbacks (QPair / pool byte counters).
        Idempotent and cheap after the first call; everything before it —
        dispatch, stacking, even the cluster's scatter — is free of host
        synchronization. Benchmarks call it inside the timed region so
        they measure completed work, never async dispatch."""
        if self._raw is not None:
            raw, self._raw = self._raw, None
            if self.kind == "rows":
                self._count = int(raw["count"])
                self._rows = raw["rows"]
                if self._meta.get("cm"):
                    # column-major (w, n) payload, laid out as rows on the
                    # host. Only the survivors cross, unless the response
                    # is encrypted: then its tail is keystream, not zeros.
                    w, n = self._rows.shape
                    ship = n if self._meta.get("sealed") else self._count
                    with TraceAnnotation("fv.d2h"):
                        cols = np.asarray(self._rows[:, :ship])
                    with TraceAnnotation("fv.layout"):
                        rows = np.zeros((n, w), np.float32)
                        rows[:ship] = cols.T
                    self._rows = rows
                self._shipped = int(raw["shipped"])
                if "ids" in raw:
                    with TraceAnnotation("fv.d2h"):
                        ids = np.asarray(raw["ids"][: self._count])
                    self._ids = np.rint(ids).astype(np.int64)
            elif self.kind == "mask":
                self._mask = raw["mask"]
                self._shipped = int(raw["shipped"])
            elif self.kind == "scalars":
                with TraceAnnotation("fv.d2h"):
                    limbs = np.asarray(raw["limbs"])
                count, sums = kref.limb_totals(limbs, self._meta["n_terms"])
                self._count = count
                self._scalars = {"count": count, "sums": sums}
                self._shipped = int(raw["shipped"])
            else:
                self._finalize_groups(raw)
        if self._callbacks:
            cbs, self._callbacks = self._callbacks, []
            for cb in cbs:
                cb(self)
        return self

    def _finalize_groups(self, raw: dict) -> None:
        # the paper's collision buffer: overflow rows ship to the client
        # for software post-aggregation. The executable already packed them
        # to the front of ovf_keys/ovf_vals (device-side compaction), so
        # only the `ovf_count` collision rows cross to the host — never the
        # partition-sized key/value arrays.
        n_ovf = int(raw["ovf_count"])
        with TraceAnnotation("fv.d2h"):
            ovf_keys = np.asarray(raw["ovf_keys"][:n_ovf])
            ovf_vals = (np.asarray(raw["ovf_vals"][:, :n_ovf]).T
                        if self._meta.get("cm")
                        else np.asarray(raw["ovf_vals"][:n_ovf]))
        self._groups = dict(
            bucket_keys=raw["bucket_keys"], count=raw["count"],
            sum=raw["sum"], min=raw["min"], max=raw["max"],
            drop_key=self._meta.get("drop_key"),
            ovf_keys=ovf_keys, ovf_vals=ovf_vals)
        self._shipped = int(raw["shipped"])


class CompiledPipeline:
    """One fused jit executable per (schema layout, pipeline signature)."""

    def __init__(self, schema: FTable, pipeline: tuple,
                 interpret: bool | None, tiered: bool = False):
        pipeline = op_ir.validate_pipeline(tuple(pipeline))
        self.signature = op_ir.signature(pipeline)
        # tiered executables take the pool's decode descriptors as an extra
        # operand and fuse the cold-page decompress into the same dispatch
        # (kernels/tier.py); `tiered` is part of the compile-cache key, so
        # flat-DRAM pipelines keep their exact pre-tiering trace.
        self.tiered = bool(tiered)
        # interpret=True means "no real Pallas backend": lower the operators
        # to XLA-native implementations instead of emulating the MXU.
        self.interpret = (interpret if interpret is not None
                          else jax.default_backend() != "tpu")
        self.traces = 0          # trace-time counter (cache-regression tests)
        self._cols = tuple(c.name for c in schema.columns)
        self._n_cols = len(self._cols)
        self._str_width = schema.str_width

        # --- resolve static plan (one-time, off the hot path) ---------------
        a = self._n_cols or 1
        preds: list = []
        self.proj_mask = np.ones((a,), np.float32)
        self.proj_cols: list[int] | None = None
        self.smart = False
        self.regex_tbl = None
        self.group: op_ir.GroupBy | None = None
        self.distinct: op_ir.Distinct | None = None
        self.crypt_pre: op_ir.Crypt | None = None
        self.crypt_post: op_ir.Crypt | None = None
        self.join: op_ir.JoinSmall | None = None
        self.agg: op_ir.Aggregate | None = None
        self.has_select = False

        for op in pipeline:
            if isinstance(op, op_ir.Project):
                self.proj_cols = [self._col(c) for c in op.cols]
                self.proj_mask = np.zeros((self._n_cols,), np.float32)
                self.proj_mask[self.proj_cols] = 1.0
            elif isinstance(op, op_ir.SmartAddress):
                self.proj_cols = [self._col(c) for c in op.cols]
                self.smart = True
            elif isinstance(op, op_ir.Select):
                self.has_select = True
                preds.extend(op.predicates)
            elif isinstance(op, op_ir.RegexMatch):
                self.regex_tbl = compile_regex(op.pattern)
            elif isinstance(op, op_ir.JoinSmall):
                self.join = op
            elif isinstance(op, op_ir.GroupBy):
                self.group = op
            elif isinstance(op, op_ir.Distinct):
                self.distinct = op
            elif isinstance(op, op_ir.Aggregate):
                self.agg = op
            elif isinstance(op, op_ir.Crypt):
                if op.when == "pre":
                    self.crypt_pre = op
                else:
                    self.crypt_post = op
            elif isinstance(op, op_ir.Pack):
                pass

        if self.join is not None and (self.group is not None
                                      or self.distinct is not None):
            raise ValueError("JoinSmall composes with select/project only")

        # predicate slots, (A, S): as many as the busiest column needs
        slots = op_ir.column_slots(preds)
        n_slots = max([1] + [len(v) for v in slots.values()])
        self.sel_ops = np.zeros((a, n_slots), np.int32)
        self.sel_vals = np.zeros((a, n_slots), np.float32)
        for name, col_slots in slots.items():
            i = self._col(name)
            for k, (code, value) in enumerate(col_slots):
                self.sel_ops[i, k] = op_ir.OPS[code]
                self.sel_vals[i, k] = value

        # aggregate terms: exact only over i32 columns (|x| < 2^24)
        self.agg_terms: tuple = ()
        if self.agg is not None:
            for term in self.agg.sums:
                for c in term:
                    dtype = schema.columns[self._col(c)].dtype
                    if dtype != "i32":
                        raise op_ir.AggregateTermError(
                            f"Aggregate term {term!r}: column {c!r} is "
                            f"{dtype}, not i32: its sum would not be exact")
            self.agg_terms = tuple(tuple(self._col(c) for c in term)
                                   for term in self.agg.sums)

        # the kernel lowering holds a word table column-major, (A, n)
        self._cm = not self.interpret and not self._str_width

        self.kind = ("mask" if self.regex_tbl is not None else
                     "groups" if (self.group is not None
                                  or self.distinct is not None) else
                     "scalars" if self.agg is not None else "rows")

        # --- the fused executables (shape-specialized lazily by jit) --------
        # Bound methods on purpose: every attribute the entries read is
        # assigned exactly once, above, and never reassigned after __init__,
        # so the traced capture cannot go stale.
        # farlint: ok jit-closure -- captured attrs are write-once (__init__)
        self._jit_rows = jax.jit(self._rows_entry)
        # farlint: ok jit-closure -- captured attrs are write-once (__init__)
        self._jit_pages = jax.jit(self._pages_entry,
                                  static_argnames=("n_rows", "row_words",
                                                   "page_words"))
        # farlint: ok jit-closure -- captured attrs are write-once (__init__)
        self._jit_strings = jax.jit(self._strings_entry)

    def _col(self, name: str) -> int:
        try:
            return self._cols.index(name)
        except ValueError:
            raise KeyError(f"no column {name!r}") from None

    @property
    def response_width(self) -> int:
        """Column count of the packed rows-kind response buffer: narrowed
        to the projection under smart addressing, otherwise the full table
        width plus (for joins) the appended build columns and the zeroed
        hit column. The single source of truth for response shape — the
        scatter-gather merge uses it to build empty results that match
        what `_body` would have packed."""
        if self.smart and self.proj_cols is not None:
            return len(self.proj_cols)
        width = self._n_cols
        if self.join is not None:
            width += len(self.join.build_cols) + 1
        return width

    # ------------------------------------------------------------ public API
    def __call__(self, rows, lengths=None, build=None,
                 row_ids=None) -> PipelineResult:
        """Compatibility path: rows already materialized (offload engine,
        string tables). Still one fused traced program. `row_ids` (optional,
        (n,) i32) are the rows' indices in the original un-partitioned
        table: they key the positional CTR keystream and ride the packing
        as survivor ids (see _body)."""
        rows = jnp.asarray(rows)
        n = int(rows.shape[0])
        payload = self._jit_rows(
            rows, None if lengths is None else jnp.asarray(lengths),
            self._as_build(build), self._as_ids(row_ids))
        if self._columnar_read():
            read_bytes = n * len(self.proj_cols) * WORD_BYTES
        else:
            read_bytes = int(np.prod(rows.shape)) * (
                1 if self._str_width else WORD_BYTES)
        return self._wrap(payload, read_bytes)

    def run_pages(self, buf, pages, n_valid, build=None, *,
                  n_rows: int, row_words: int,
                  row_ids=None, tier=None, page_words: int | None = None,
                  read_bytes: int | None = None) -> PipelineResult:
        """The fused request verb: ONE dispatch does page gather + pipeline.

        buf: pool buffer (n_pages, page_words); pages: (P,) page ids;
        n_valid: traced row-validity scalar (rows >= n_valid are masked);
        row_ids: optional (n_rows,) original-table row indices (partition
        dispatch — keystream offsets + survivor-id packing). On a tiered
        pipeline, `tier` is the pool's decode-descriptor tuple
        (`FarPool.tier_desc`) and `page_words` the static frame width: the
        cold-page decompress fuses into the SAME dispatch. `read_bytes`
        overrides the logical read accounting with the physical
        (compressed) bytes the tiered gather actually pulls.
        """
        payload = self._jit_pages(
            buf, jnp.asarray(pages, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), self._as_build(build),
            self._as_ids(row_ids), self._as_tier(tier),
            n_rows=n_rows, row_words=row_words, page_words=page_words)
        return self._wrap(payload,
                          self._pages_read_bytes(n_rows, row_words)
                          if read_bytes is None else read_bytes)

    def run_pages_batched(self, buf, pages, n_valid, build=None, *,
                          n_rows: int, row_words: int,
                          row_ids=None, tier=None,
                          page_words: int | None = None,
                          read_bytes: list[int] | None = None
                          ) -> list[PipelineResult]:
        """Stacked multi-client dispatch: pages (B, P), n_valid (B,).

        One vmapped executable serves the whole scheduling round; the
        payload is split back into per-client lazy results. `n_rows` is the
        round's shape bucket — per-request tables may be smaller; their page
        lists are padded (pool null page) and their tails masked by
        `n_valid`. A shared join `build=(keys, vals)` operand is broadcast
        (closed over, not vmapped) across the stack. Read/shipped byte
        accounting is per-request: padded rows are never billed (read bytes
        come from each request's `n_valid`, shipped bytes from traced
        counts that already exclude masked rows), and each request's row /
        mask arrays are sliced back to its own length.
        """
        pages = jnp.asarray(pages, jnp.int32)
        nv = np.asarray(n_valid, np.int64)
        payload = self._jit_pages(
            buf, pages, jnp.asarray(n_valid, jnp.int32),
            self._as_build(build), self._as_ids(row_ids),
            self._as_tier(tier),
            n_rows=n_rows, row_words=row_words, page_words=page_words)
        return [self._wrap(self._split(payload, b, int(nv[b])),
                           self._pages_read_bytes(int(nv[b]), row_words)
                           if read_bytes is None else read_bytes[b])
                for b in range(int(pages.shape[0]))]

    def run_strings_batched(self, strings, lengths, n_valid, *,
                            widths=None, row_ids=None) -> list[PipelineResult]:
        """Stacked string/regex dispatch: strings (B, n, w) uint8 bytes,
        lengths (B, n) int32, n_valid (B,) valid-row counts.

        The DFA/crypt body is vmapped over the stack — one executable per
        scheduling round regardless of how many clients submitted. Rows
        past a request's `n_valid` (bucket padding) are masked out of the
        match mask and excluded from shipped/read accounting; `widths`
        (per-request pre-padding byte widths) keeps read accounting exact
        under width bucketing.
        """
        strings = jnp.asarray(strings, jnp.uint8)
        nv = np.asarray(n_valid, np.int64)
        payload = self._jit_strings(
            strings, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), self._as_ids(row_ids))
        w = int(strings.shape[2])
        ws = (np.full((strings.shape[0],), w, np.int64) if widths is None
              else np.asarray(widths, np.int64))
        return [self._wrap(self._split(payload, b, int(nv[b])),
                           int(nv[b]) * int(ws[b]))
                for b in range(int(strings.shape[0]))]

    def _split(self, payload: dict, b: int, nv: int) -> dict:
        """Request b's slice of a stacked payload. Row-shaped arrays are cut
        back to the request's own length so bucket padding is invisible to
        the client (packed survivors always fit: count <= nv); column-major
        ones along their last axis."""
        out = {}
        for k, v in payload.items():
            v = v[b]
            if k in ("rows", "mask", "ovf_keys", "ovf_vals", "ids"):
                # packed fronts always fit: count <= nv
                v = v[..., :nv] if self._cm and v.ndim == 2 else v[:nv]
            out[k] = v
        return out

    # -------------------------------------------------------------- internals
    @staticmethod
    def _as_ids(row_ids):
        return None if row_ids is None else jnp.asarray(row_ids, jnp.int32)

    def _as_tier(self, tier):
        if (tier is None) == self.tiered:
            raise ValueError("tiered pipelines take a tier descriptor "
                             "operand; flat pipelines take none")
        return tier

    @property
    def read_cols(self) -> tuple[int, ...] | None:
        """Column indices a column-granular gather touches, or None when
        the plan reads full rows — what the tiered dispatch passes to
        `FarPool.tier_read_bytes` so physical billing matches the gather."""
        return tuple(self.proj_cols) if self._columnar_read() else None

    @staticmethod
    def _as_build(build):
        if build is None:
            return None
        bkeys = jnp.asarray(build[0], jnp.int32)
        # the uniqueness contract is checked here, eagerly, because inside
        # the traced body the keys are Tracers and the check would be a
        # silent no-op (hash_join_xla picks an arbitrary duplicate)
        if not isinstance(bkeys, jax.core.Tracer):
            # The traced path (Tracer) skips this branch, so the eager
            # sync only happens once at build registration.
            # farlint: ok host-sync -- deliberate eager uniqueness check
            bknp = np.asarray(bkeys)
            if len(np.unique(bknp)) != len(bknp):
                raise ValueError(
                    "build keys must be unique for a small-table join")
        return (bkeys, jnp.asarray(build[1], jnp.float32))

    def _columnar_read(self) -> bool:
        """True when the plan actually gathers column-granular (a
        pre-decrypt forces full-row reads: the CTR keystream is positional
        over the row) — the read accounting must match the gather."""
        return (self.smart and self.proj_cols is not None
                and self.crypt_pre is None and self.regex_tbl is None)

    def _pages_read_bytes(self, n_rows: int, row_words: int) -> int:
        if self._columnar_read():
            # column-granular DRAM reads (paper §5.2, Fig. 7)
            return n_rows * len(self.proj_cols) * WORD_BYTES
        return n_rows * row_words * WORD_BYTES

    def _wrap(self, payload: dict, read_bytes: int) -> PipelineResult:
        # drop_key is always published: select masking AND n_valid tail
        # masking both remap dropped rows to _DROP_KEY, and real keys can
        # never collide with it (ingest enforces |key| < 2^24).
        meta = {"cm": self._cm, "sealed": self.crypt_post is not None}
        if self.kind == "groups":
            meta["drop_key"] = _DROP_KEY
        if self.kind == "scalars":
            meta["n_terms"] = len(self.agg_terms)
        return PipelineResult(self.kind, read_bytes=read_bytes,
                              _raw=payload, _meta=meta)

    def _rows_entry(self, rows, lengths, build, row_ids):
        work = rows.T if self._cm else rows
        return self._body(work, lengths, None, build, row_ids, narrowed=False)

    def _strings_entry(self, strings, lengths, n_valid, row_ids):
        # stacked (B, n, w) byte tensor: vmap the whole DFA/crypt body
        if row_ids is None:
            def one(s, ln, nv):
                return self._body(s, ln, nv, None, None, narrowed=False)
            return jax.vmap(one)(strings, lengths, n_valid)

        def one(s, ln, nv, ids):
            return self._body(s, ln, nv, None, ids, narrowed=False)
        return jax.vmap(one)(strings, lengths, n_valid, row_ids)

    def _pages_entry(self, buf, pages, n_valid, build, row_ids, tier, *,
                     n_rows, row_words, page_words):
        # A flat pool's pages are copied out once, before any vmap (see
        # `pool.read_pages`); a tiered gather decodes from the whole buffer.
        src = buf if tier is not None else fpool.read_pages(buf, pages)

        def one(src, nv, ids, tr):
            return self._gather_run(src, nv, build, ids, n_rows, row_words,
                                    tr, page_words)
        if pages.ndim == 1:
            return one(src, n_valid, row_ids, tier)
        # stacked multi-client round: `build` is closed over, not vmapped —
        # the round shares ONE join build table, broadcast across the
        # stacked probes. `tier` (when present) is a stacked descriptor
        # tuple and maps with the pages — each request decodes its own
        # cold planes inside the same vmapped body, from the shared buffer.
        axes = (None if tier is not None else 0, 0,
                None if row_ids is None else 0, None if tier is None else 0)
        return jax.vmap(one, in_axes=axes)(src, n_valid, row_ids, tier)

    def _gather_run(self, src, n_valid, build, row_ids,
                    n_rows, row_words, tier=None, page_words=None):
        """`src` is the request's copied pages (P, page_words) on a flat
        pool, the whole pool buffer on a tiered one."""
        narrowed = self._columnar_read()
        cols = (tuple(self.proj_cols) if narrowed
                else tuple(range(row_words)))
        if tier is not None:
            work = (ktier.gather_columns_tiered(src, tier, n_rows, row_words,
                                                cols, page_words)
                    if narrowed else
                    ktier.gather_rows_tiered(src, tier, n_rows, row_words,
                                             page_words))
            work = work.T if self._cm else work
        elif self._cm:
            work = fpool.columns_of(src, n_rows, row_words, cols)
        elif narrowed:
            work = fpool.columns_of(src, n_rows, row_words, cols).T
        else:
            work = fpool.rows_of(src, n_rows, row_words)
        return self._body(work, None, n_valid, build, row_ids,
                          narrowed=narrowed)

    def _body(self, work, lengths, n_valid, build, row_ids, *,
              narrowed: bool):
        """The whole request pipeline as one traced program.

        `work` is (n, A) rows, or column-major (A, n) on the kernel
        lowering of a word table (`_cm`): there every array stays
        lane-dense, where (n, A) would be padded to 128 lanes on the TPU.
        """
        self.traces += 1                         # trace-time side effect only
        xla = self.interpret                     # lowering choice (static)
        cm = self._cm
        ax = 0 if cm else 1                      # the column axis of `work`
        n = work.shape[1 - ax]
        valid = (None if n_valid is None
                 else jnp.arange(n, dtype=jnp.int32) < n_valid)

        # -- pre-decrypt (data at rest is encrypted; cipher on read stream) --
        if self.crypt_pre is not None:
            key = np.asarray(self.crypt_pre.key, np.uint32)
            nonce = self.crypt_pre.nonce
            if self._str_width:
                u32 = work.astype(jnp.uint32)
            else:
                u32 = jnp.asarray(work, jnp.float32).view(jnp.uint32)
            if row_ids is not None:
                # partitioned dispatch: this node holds a row *subset* of
                # one encrypted table, so each row's keystream position is
                # its offset in the ORIGINAL row-major flattening, not the
                # local one. Gathered keystream goes through the pure-jnp
                # reference cipher (backend-agnostic; the Pallas kernel
                # assumes a contiguous stream).
                w = work.shape[ax]
                base = row_ids.astype(jnp.uint32) * jnp.uint32(w)
                word = jnp.arange(w, dtype=jnp.uint32)
                idx = (base[None, :] + word[:, None] if cm
                       else base[:, None] + word[None, :])
                dec = kref.ctr_crypt(u32.reshape(-1), jnp.asarray(key), nonce,
                                     idx=idx.reshape(-1)).reshape(u32.shape)
            elif xla:
                dec = kref.ctr_crypt(u32.reshape(-1), jnp.asarray(key),
                                     nonce).reshape(u32.shape)
            elif cm:
                dec = kops.crypt_cols(u32, key, nonce)
            else:
                dec = kops.crypt(u32.reshape(-1), key,
                                 nonce).reshape(u32.shape)
            work = (dec.view(jnp.float32) if not self._str_width
                    else dec.astype(jnp.uint8))

        # -- regex path (string tables) --------------------------------------
        if self.regex_tbl is not None:
            table, accept = self.regex_tbl
            if xla:
                mask = kref.dfa_match(work, lengths, jnp.asarray(table),
                                      jnp.asarray(accept))
            else:
                mask = kops.regex_match(work, lengths, jnp.asarray(table),
                                        jnp.asarray(accept))
            if valid is not None:
                mask = mask & valid
                # 1 byte/row decision for *valid* rows only (bucket padding
                # must not inflate the response accounting)
                return {"mask": mask,
                        "shipped": jnp.sum(valid.astype(jnp.int32))}
            # 1 byte/row decision + matched rows
            return {"mask": mask, "shipped": jnp.int32(n)}

        # -- smart addressing narrows columns (unless gathered narrowed) -----
        if self.smart and self.proj_cols is not None:
            if not narrowed:
                work = jnp.take(work, np.asarray(self.proj_cols), axis=ax)
            eff_sel_ops = self.sel_ops[np.asarray(self.proj_cols)]
            eff_sel_vals = self.sel_vals[np.asarray(self.proj_cols)]
            eff_proj = np.ones((len(self.proj_cols),), np.float32)
            terms = tuple(tuple(self.proj_cols.index(c) for c in term)
                          for term in self.agg_terms)
        else:
            eff_sel_ops = self.sel_ops
            eff_sel_vals = self.sel_vals
            eff_proj = self.proj_mask
            terms = self.agg_terms

        # -- scalar aggregate: count and exact sums, nothing packed ----------
        if self.agg is not None:
            if xla:
                limbs = kops.select_aggregate_xla(
                    work, eff_sel_ops, eff_sel_vals, terms, valid)
            else:
                limbs = kops.select_aggregate_cols(
                    work, eff_sel_ops, eff_sel_vals, terms, n_valid)
            # the count and each sum, 8 bytes each
            shipped = np.int32((1 + len(terms)) * 8)
            return {"limbs": limbs, "shipped": shipped}

        # -- small-table join: matched build values + a hit column,
        # expressed as extra predicate/projection columns so the fused
        # select/project does the packing ------------------------------------
        has_join = self.join is not None
        if has_join:
            if build is None:
                raise ValueError("JoinSmall needs build=(keys, vals)")
            bkeys, bvals = build
            pkeys = jnp.rint(jnp.take(work, self._col(self.join.probe_key),
                                      axis=ax)).astype(jnp.int32)
            if xla:
                joined, hit = kops.hash_join_xla(pkeys, bkeys, bvals)
            else:
                joined, hit = kops.hash_join_cols(pkeys, bkeys, bvals)
            nb = joined.shape[ax]
            work = jnp.concatenate(
                [work, joined, jnp.expand_dims(hit.astype(jnp.float32), ax)],
                axis=ax)
            hit_ops = np.zeros((nb + 1, eff_sel_ops.shape[1]), np.int32)
            hit_ops[nb, 0] = op_ir.OPS["=="]
            hit_vals = np.zeros(hit_ops.shape, np.float32)
            hit_vals[nb, 0] = 1.0
            eff_sel_ops = np.concatenate([eff_sel_ops, hit_ops])
            eff_sel_vals = np.concatenate([eff_sel_vals, hit_vals])
            eff_proj = np.concatenate(
                [eff_proj, np.ones(nb, np.float32),
                 np.zeros(1, np.float32)])      # keep build cols, drop hit

        # -- grouping ---------------------------------------------------------
        if self.group is not None or self.distinct is not None:
            return self._group_body(work, eff_sel_ops, eff_sel_vals, valid,
                                    xla)

        # response width BEFORE any bookkeeping columns are appended
        ncols_out = (len(self.proj_cols)
                     if (self.proj_cols is not None and self.smart)
                     else int(np.sum(eff_proj)))

        # -- survivor-id column: partitioned dispatch threads each row's
        # original-table index through the packing (predicate-skipped,
        # projection-kept), so the client-side gather can splice partials
        # back into single-node row order. Split off before the response
        # encrypt — ids are transport metadata, not response payload. -------
        if row_ids is not None:
            work = jnp.concatenate(
                [work, jnp.expand_dims(row_ids.astype(jnp.float32), ax)],
                axis=ax)
            eff_sel_ops = np.concatenate(
                [eff_sel_ops, np.zeros((1, eff_sel_ops.shape[1]), np.int32)])
            eff_sel_vals = np.concatenate(
                [eff_sel_vals,
                 np.zeros((1, eff_sel_vals.shape[1]), np.float32)])
            eff_proj = np.concatenate([eff_proj, np.ones(1, np.float32)])

        # -- selection + projection + packing (fused) -------------------------
        if xla:
            packed, count = kops.select_project_xla(
                work, eff_sel_ops, eff_sel_vals, eff_proj, valid)
        else:
            packed, count = kops.select_project_cols(
                work, eff_sel_ops, eff_sel_vals, eff_proj, n_valid)

        ids_packed = None
        if row_ids is not None:
            width = packed.shape[ax] - 1
            ids_packed = jnp.take(packed, width, axis=ax)
            packed = jax.lax.slice_in_dim(packed, 0, width, axis=ax)

        # -- post-encrypt + pack ----------------------------------------------
        if self.crypt_post is not None:
            key = np.asarray(self.crypt_post.key, np.uint32)
            u32 = packed.view(jnp.uint32)
            if xla:
                enc = kref.ctr_crypt(u32.reshape(-1), jnp.asarray(key),
                                     self.crypt_post.nonce).reshape(u32.shape)
            else:
                enc = kops.crypt_cols(u32, key, self.crypt_post.nonce)
            packed = enc.view(jnp.float32)

        shipped = count.astype(jnp.int32) * np.int32(ncols_out * WORD_BYTES)
        out = {"rows": packed, "count": count, "shipped": shipped}
        if ids_packed is not None:
            out["ids"] = ids_packed
        return out

    def _group_body(self, work, eff_sel_ops, eff_sel_vals, valid, xla):
        if self.group is not None:
            kcol = self._col(self.group.key)
            vcols = [self._col(c) for c in self.group.values]
            nb = self.group.n_buckets
        else:
            kcol = self._col(self.distinct.cols[0])
            vcols = [kcol]
            nb = self.distinct.n_buckets
        ax = 0 if self._cm else 1                # the column axis of `work`
        keys = jnp.rint(jnp.take(work, kcol, axis=ax)).astype(jnp.int32)
        vals = jnp.take(work, np.asarray(vcols), axis=ax)
        # grouping consumes only selected+valid rows: mask via sentinel key
        m = None
        if self.has_select:
            m = kref.eval_predicate(work, jnp.asarray(eff_sel_ops),
                                    jnp.asarray(eff_sel_vals), col_axis=ax)
        if valid is not None:
            m = valid if m is None else (m & valid)
        if m is not None:
            keys = jnp.where(m, keys, _DROP_KEY)
            vals = jnp.where(jnp.expand_dims(m, ax), vals, 0)
        if xla:
            res = kref.group_aggregate(keys, vals, nb)
        else:
            res = kops.group_aggregate_cols(keys, vals, n_buckets=nb)
        ovf = res["overflow_mask"]
        keep = ovf & (keys != _DROP_KEY)
        keep_cnt = jnp.sum(keep.astype(jnp.int32))
        # compact (keys, values) collision partial: overflow rows packed to
        # the front IN the traced program (stable two-way partition via the
        # composite-key sort), so the response ships B buckets + the actual
        # collision rows — the host never touches partition-sized arrays
        with jax.named_scope("fv.ovf_pack"):
            order, _ = kref.sort_by_bucket((~keep).astype(jnp.int32), 2)
            ovf_keys = keys[order]
            ovf_vals = (kref.take_lanes(vals, order) if self._cm
                        else vals[order])
        shipped = (np.int32(nb * (2 + 4 * len(vcols)) * WORD_BYTES)
                   + keep_cnt * np.int32((1 + len(vcols)) * WORD_BYTES))
        return {"bucket_keys": res["bucket_keys"], "count": res["count"],
                "sum": res["sum"], "min": res["min"], "max": res["max"],
                "ovf_keys": ovf_keys, "ovf_vals": ovf_vals,
                "ovf_count": keep_cnt, "shipped": shipped}


_CACHE: dict = {}                # guarded-by: _CACHE_LOCK
_CACHE_LOCK = threading.Lock()   # cluster nodes flush from parallel threads


def compile_pipeline(schema: FTable, pipeline: tuple,
                     *, interpret: bool | None = None,
                     tiered: bool = False) -> CompiledPipeline:
    """Fetch (or build) the fused executable for (schema layout, signature).

    The key deliberately excludes the table *name*: two clients running the
    same pipeline over same-layout tables share one executable, which is
    what lets the node's scheduler coalesce them into a stacked dispatch.
    `interpret` is normalized to its resolved boolean before keying, so
    `interpret=None` (auto) and an explicit matching bool share the entry.
    `tiered=True` keys a SEPARATE executable whose gather takes the pool's
    decode descriptors and inflates cold pages in-dispatch — flat tables
    never pay for the decode arithmetic, and flipping a table's tier flips
    which cached executable serves it (a cache lookup, like any other
    "partial reconfiguration").
    """
    pipeline = op_ir.validate_pipeline(tuple(pipeline))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # str_width enters the key only as string-vs-word: the traced program
    # never bakes the width in (shapes are jit-specialized per call), so
    # different-width string tables share one executable — which is what
    # lets the scheduler width-bucket stacked regex rounds.
    key = (tuple((c.name, c.dtype) for c in schema.columns),
           bool(schema.str_width), op_ir.signature(pipeline), interpret,
           bool(tiered))
    # One build per key under concurrent flushes. The whole get-or-build
    # runs under the lock: the old lock-free fast path read the dict while
    # parallel drains were inserting, and a racing reader could see a
    # half-initialized slot. Construction is cheap (jit wrapper creation;
    # tracing happens at first call), so serializing builds costs nothing.
    with _CACHE_LOCK:
        pipe = _CACHE.get(key)
        if pipe is None:
            pipe = _CACHE[key] = CompiledPipeline(schema, pipeline,
                                                  interpret, tiered)
    return pipe


def cache_info() -> int:
    with _CACHE_LOCK:
        return len(_CACHE)


def clear_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()
