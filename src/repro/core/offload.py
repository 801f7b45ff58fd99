"""Near-data offload engine: pipelines over a device-sharded pool (paper §4).

The single-node FarPool covers the paper's actual prototype (one FPGA node).
This module is the scale-out: the table's row matrix is sharded over the
mesh's pool axis (default "model"); `shard_map` runs the compiled pipeline
*on the device that owns the shard* (near-data), and only the reduced
results are exchanged:

  * rows kind:    per-shard packed survivors + counts are all-gathered
                  (variable-length response packets, like the RDMA sender);
  * groups kind:  per-shard partial aggregates (fixed B buckets) are shipped
                  and merged client-side — the multi-node generalization of
                  the paper's single hash table;
  * mask kind:    1 byte/row decisions.

`shipped_fraction` quantifies the data-movement reduction vs. fetching raw
rows — the metric behind Figs. 8-10.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import operators as op_ir
from repro.core.pipeline import PipelineResult, compile_pipeline
from repro.core.table import FTable, WORD_BYTES
from repro.kernels import ref as kref

# group-merge pad key: sorts past every real key (|key| < 2^24 at ingest),
# the bucket sentinel (int32 min) and the drop key (int32 min + 1)
_PAD_KEY = np.int32(np.iinfo(np.int32).max)
_BIG = np.float32(np.finfo(np.float32).max)


@jax.jit
def _segment_merge_groups(keys, cnt, sums, mins, maxs):
    """Fused device-side merge of concatenated group partials.

    keys (M,) i32 (invalid entries pre-masked to _PAD_KEY); cnt (M,) i32;
    sums/mins/maxs (M, V) f32. Stable-sorts by key and reduces each key's
    segment in ONE log-depth segmented scan — the multi-node generalization
    of the paper's client-side software merge, but as a single jitted
    dispatch instead of a Python dict loop over every bucket of every
    partial. Returns per-row (sorted_keys, end_mask, count, sum, min, max);
    each key's totals sit at its segment-end row (select with end_mask).
    """
    order = jnp.argsort(keys, stable=True)
    k = keys[order]
    n = k.shape[0]
    one = jnp.ones((min(n, 1),), bool)
    flags = jnp.concatenate([one, k[1:] != k[:-1]])
    cs, ss, mns, mxs = kref.segmented_reduce(
        sums[order], mins[order], maxs[order], flags, counts=cnt[order])
    end = jnp.concatenate([flags[1:], one])
    return k, end, cs, ss, mns, mxs


# farlint: finalize-boundary (the group merge IS the designed sync point)
def merge_groups_device(groups: "list[dict]",
                        drop: "int | None") -> dict:
    """Concatenate N partials' (bucket entries + overflow rows) and
    segment-reduce them device-side; only the compact per-key totals cross
    back to the host dict. Overflow rows ride the same path as the bucket
    partials: a collision row is just a (key, count=1, sum=min=max=value)
    partial aggregate."""
    drop_val = np.int32(_PAD_KEY if drop is None else drop)
    ks, cs, ss, mns, mxs = [], [], [], [], []
    for g in groups:
        bk = jnp.asarray(g["bucket_keys"], jnp.int32)
        cnt = jnp.asarray(g["count"], jnp.int32)
        bsum = jnp.asarray(g["sum"], jnp.float32)
        bad = ((bk == np.int32(kref.KEY_SENTINEL)) | (cnt <= 0)
               | (bk == drop_val))
        ks.append(jnp.where(bad, _PAD_KEY, bk))
        cs.append(jnp.where(bad, 0, cnt))
        ss.append(jnp.where(bad[:, None], 0.0, bsum))
        mns.append(jnp.where(bad[:, None], _BIG,
                             jnp.asarray(g["min"], jnp.float32)))
        mxs.append(jnp.where(bad[:, None], -_BIG,
                             jnp.asarray(g["max"], jnp.float32)))
        ok = jnp.asarray(g["ovf_keys"], jnp.int32)
        if ok.shape[0]:
            ov = jnp.asarray(g["ovf_vals"], jnp.float32)
            obad = ok == drop_val
            ks.append(jnp.where(obad, _PAD_KEY, ok))
            cs.append(jnp.where(obad, 0, 1).astype(jnp.int32))
            ks_bad = obad[:, None]
            ss.append(jnp.where(ks_bad, 0.0, ov))
            mns.append(jnp.where(ks_bad, _BIG, ov))
            mxs.append(jnp.where(ks_bad, -_BIG, ov))
    m = sum(int(a.shape[0]) for a in ks)
    pad = op_ir.pow2_bucket(m) - m      # bound jit retraces across shapes
    v = int(ss[0].shape[1])
    if pad:
        ks.append(jnp.full((pad,), _PAD_KEY, jnp.int32))
        cs.append(jnp.zeros((pad,), jnp.int32))
        ss.append(jnp.zeros((pad, v), jnp.float32))
        mns.append(jnp.full((pad, v), _BIG, jnp.float32))
        mxs.append(jnp.full((pad, v), -_BIG, jnp.float32))
    keys = jnp.concatenate(ks)
    cnt = jnp.concatenate(cs)
    sums = jnp.concatenate(ss)
    mins = jnp.concatenate(mns)
    maxs = jnp.concatenate(mxs)
    k, end, tc, tsum, tmin, tmax = _segment_merge_groups(
        keys, cnt, sums, mins, maxs)
    sel = np.asarray(end) & (np.asarray(k) != _PAD_KEY)
    uk = np.asarray(k)[sel]
    uc = np.asarray(tc)[sel]
    us = np.asarray(tsum)[sel]
    umn = np.asarray(tmin)[sel]
    umx = np.asarray(tmax)[sel]
    return {int(key): [int(c), s, mn, mx]
            for key, c, s, mn, mx in zip(uk.tolist(), uc.tolist(),
                                         us, umn, umx)}


@dataclass
class OffloadResult:
    result: PipelineResult
    raw_bytes: int              # what a no-pushdown fetch would ship
    shipped_bytes: int          # what push-down actually ships

    @property
    def shipped_fraction(self) -> float:
        return self.shipped_bytes / max(1, self.raw_bytes)


def shard_table(mesh: Mesh, axis: str, rows: jnp.ndarray) -> jnp.ndarray:
    """Place a row matrix row-sharded over the pool axis (striping)."""
    n = rows.shape[0]
    size = mesh.shape[axis]
    pad = (-n) % size
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    return jax.device_put(rows, NamedSharding(mesh, P(axis, None)))


def run_offloaded(mesh: Mesh, axis: str, schema: FTable, pipeline: tuple,
                  rows_sharded: jnp.ndarray, n_valid: int,
                  *, interpret: bool | None = None) -> OffloadResult:
    """Execute pipeline near-data on every pool shard, merge client-side."""
    pipe = compile_pipeline(schema, tuple(pipeline), interpret=interpret)
    nshards = mesh.shape[axis]
    n_padded = rows_sharded.shape[0]
    per = n_padded // nshards

    # valid row counts per shard (tail shards may hold padding)
    starts = np.arange(nshards) * per
    valid = np.clip(n_valid - starts, 0, per).astype(np.int32)

    # Run the pipeline per shard. We express this as a simple loop over
    # shard slices rather than shard_map because the pipeline returns
    # host-side dicts (client merge); the dry-run/serving paths use the
    # jit'd shard_map far-KV engine instead. Device placement still holds:
    # each slice is resident on its owning device and the kernel executes
    # there (XLA keeps computation where operands live).
    partials: list[PipelineResult] = []
    for s in range(nshards):
        local = jax.lax.slice_in_dim(rows_sharded, s * per, (s + 1) * per)
        if schema.str_width:
            # string tables carry lengths in the last column? lengths are
            # provided by the caller via closure in client.py path.
            raise ValueError("string tables use run_offloaded_strings")
        # mask padding rows inside each shard: pipeline predicates operate on
        # valid rows only; we pass exact valid counts by slicing.
        local = local[:max(int(valid[s]), 0)]
        if local.shape[0] == 0:
            continue
        partials.append(pipe(local))

    raw_bytes = n_valid * schema.row_words * WORD_BYTES
    return OffloadResult(result=_merge(schema, pipeline, partials),
                         raw_bytes=raw_bytes,
                         shipped_bytes=sum(p.shipped_bytes or 0
                                           for p in partials))


# farlint: finalize-boundary (client merge: survivors are host data here)
def _ctr_host(rows: np.ndarray, crypt) -> np.ndarray:
    """The CTR cipher over host rows, keystream from position 0."""
    u32 = jnp.asarray(rows.reshape(-1).view(np.uint32))
    out = kref.ctr_crypt(u32, jnp.asarray(crypt.key, jnp.uint32), crypt.nonce)
    return np.asarray(out).view(np.float32).reshape(rows.shape)


def _merge(schema: FTable, pipeline: tuple,
           partials: list[PipelineResult], *,
           n_rows: int | None = None,
           part_rows: "list[np.ndarray] | None" = None) -> PipelineResult:
    """Client-side software merge of per-shard / per-node partials.

    The base behavior (offload engine) concatenates partials in shard
    order. The cluster scatter-gather path passes two extras that make the
    merged response byte-identical to a single-node dispatch:

      n_rows      the un-partitioned table's row count: rows-kind results
                  are rebuilt as the full (n_rows, width) packed buffer
                  (survivors front, zero tail) and mask-kind results as the
                  full row mask;
      part_rows   per-partial original-row index arrays (the partition
                  map), used to scatter mask partials back to their rows.

    When every rows-kind partial carries `sel_ids` (partition dispatch),
    survivors are spliced in original-row order — hash/skew partitions
    merge as byte-exactly as contiguous range partitions. A response
    encrypted per-node (post-crypt) is decrypted with each node's local
    keystream, spliced in the clear, and re-encrypted at merged positions
    (same involutive CTR cipher; the client holds the pipeline's key)."""
    if not partials:
        # nothing was dispatched (zero-row table): the empty result must
        # still have the pipeline's kind and response width — both come
        # from the canonical compiled plan, never re-derived here
        plan = compile_pipeline(schema, tuple(pipeline))
        if plan.kind == "mask":
            return PipelineResult(
                kind="mask", mask=jnp.zeros((n_rows or 0,), bool))
        if plan.kind == "groups":
            return PipelineResult(kind="groups", groups={})
        if plan.kind == "scalars":
            return PipelineResult(kind="scalars", count=0, scalars={
                "count": 0, "sums": (0,) * len(plan.agg_terms)})
        return PipelineResult(kind="rows", rows=jnp.zeros(
            (n_rows or 0, plan.response_width), jnp.float32), count=0)
    kind = partials[0].kind
    if kind == "rows":
        # the splice runs on the host: the survivors are client data by
        # now, and an (n, w) device array with w < 128 would be padded to
        # 128 lanes on the TPU
        counts = [int(p.count) for p in partials]
        cpost = op_ir.crypt_post_of(pipeline) if n_rows is not None else None
        survivors = [np.asarray(p.rows, np.float32)[:c]
                     for p, c in zip(partials, counts)]
        if cpost is not None:
            # undo each node's local response crypt — survivors only:
            # they are packed at the front, and the keystream is
            # contiguous from position 0, so decrypt cost scales with
            # the RESULT size, not the partition size
            survivors = [_ctr_host(buf, cpost) for buf in survivors]
        rows = np.concatenate(survivors, axis=0)
        ids_list = [p.sel_ids for p in partials]
        merged_ids = None
        if all(i is not None for i in ids_list):
            merged_ids = np.concatenate(
                [np.asarray(i) for i in ids_list])
            if merged_ids.size and np.any(np.diff(merged_ids) < 0):
                # hash/skew partitions interleave; range partitions come
                # back already ordered and skip the gather entirely
                order = np.argsort(merged_ids)  # ids unique: original order
                rows = rows[order]
                merged_ids = merged_ids[order]
        count = int(rows.shape[0])
        if n_rows is not None:      # single-node-shaped packed response
            full = np.zeros((n_rows, int(rows.shape[1])), np.float32)
            full[:count] = rows
            if cpost is not None:   # re-encrypt at merged stream positions
                full = _ctr_host(full, cpost)
            rows = full
        return PipelineResult(kind="rows", rows=rows, count=count,
                              sel_ids=merged_ids,
                              shipped_bytes=sum(p.shipped_bytes or 0
                                                for p in partials),
                              read_bytes=sum(p.read_bytes for p in partials))
    if kind == "groups":
        # device-side software merge: every partial's bucket table AND its
        # collision overflow rows concatenate into one segment-reduce
        # dispatch (merge_groups_device) — the Python dict loop this
        # replaces walked N x B buckets per cluster verb and was the
        # client-side serial floor under group scale-out
        merged = merge_groups_device(
            [p.groups for p in partials],
            partials[0].groups.get("drop_key"))
        return PipelineResult(kind="groups", groups=merged,
                              shipped_bytes=sum(p.shipped_bytes or 0
                                                for p in partials),
                              read_bytes=sum(p.read_bytes for p in partials))
    if kind == "scalars":
        # exact: counts and sums are Python ints, added as such
        sums = [0] * len(partials[0].scalars["sums"])
        for p in partials:
            for i, x in enumerate(p.scalars["sums"]):
                sums[i] += int(x)
        count = sum(int(p.scalars["count"]) for p in partials)
        return PipelineResult(kind="scalars", count=count,
                              scalars={"count": count, "sums": tuple(sums)},
                              shipped_bytes=sum(p.shipped_bytes or 0
                                                for p in partials),
                              read_bytes=sum(p.read_bytes for p in partials))
    if kind == "mask":
        if part_rows is not None and n_rows is not None:
            # scatter each partition's per-row decisions back to the rows'
            # original positions (any partitioner, not just contiguous)
            full = np.zeros((n_rows,), bool)
            for p, idx in zip(partials, part_rows):
                idx = np.asarray(idx)
                full[idx] = np.asarray(p.mask)[: len(idx)]
            mask = jnp.asarray(full)
        else:
            mask = jnp.concatenate([p.mask for p in partials])
        return PipelineResult(kind="mask", mask=mask,
                              shipped_bytes=sum(p.shipped_bytes or 0
                                                for p in partials),
                              read_bytes=sum(p.read_bytes for p in partials))
    raise ValueError(kind)
