"""Distinct / group-by / aggregation kernel (paper §5.4).

TPU adaptation of Farview's cuckoo-hash + LRU-shift-register design,
restructured for scale (PR 4): the row stream is bucket-SORTED before the
kernel (stable composite-key value sort — part of the same jitted
program), bucket ownership is resolved globally on the sorted stream, and
the Pallas kernel aggregates into a SMALL, fixed number of partial bucket
tables that a tree merge combines:

  * hash lookups -> one-hot *matmuls* on the MXU, exactly as before: a
    (buckets x rows) one-hot matrix aggregates counts and sums in one dot.
  * FPGA BRAM hash tables -> per-grid-row partial bucket tables. The grid
    is (P, G): row p accumulates its G consecutive row-blocks into its own
    VMEM-resident (V, B) partial (the revisited-output accumulator
    pattern, scoped to one grid row), and P is capped at MAX_PARTIALS so
    partial memory stays P*B*V — never the O(n/block_rows * B * V) blowup
    a one-partial-per-block layout would allocate.
  * the P partials are combined by a log-depth pairwise TREE MERGE
    (`tree_merge`, plain jnp): count/sum add, min/max meet — associative,
    so any merge order is valid. Grid rows share NO state; only the
    G blocks inside a row accumulate sequentially (like the paper's
    on-chip hash state, which Farview also banks per pipeline).
  * cuckoo collision eviction -> rows whose key differs from the bucket
    owner's key are flagged *overflow* and shipped to the client for
    software post-aggregation. Ownership (first row by ORIGINAL index
    claims the bucket) is computed once, globally, on the sorted stream —
    block-local claims would disagree with the global claimant whenever a
    bucket spans a block boundary, so claims never enter the kernel.
  * the LRU shift register (hazard protection) stays unnecessary: each
    block is aggregated associatively in one step, and the tree merge has
    no read-after-write hazards at all.

Aggregates: count, sum, min, max (avg = sum/count client-side, as in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import ref

DEFAULT_BLOCK_ROWS = 256
MAX_PARTIALS = 8            # cap on partial bucket tables (VMEM/HBM bound)
_BIG = np.float32(3.0e38)
_SENT = np.int32(ref.KEY_SENTINEL)


def _block_kernel(n_buckets, bucket_ref, owns_ref, vals_ref,
                  cnt_ref, sum_ref, min_ref, max_ref):
    """Grid (P, G): partial p accumulates its g-th row-block. The output
    blocks for partial p stay resident across that row's G steps (standard
    revisited-accumulator pattern); different partials never touch each
    other's state. Rows ride the lanes; values are column-major (V, R)
    and the partial tables bucket-minor (V, B), all lane-dense."""
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        min_ref[...] = jnp.full_like(min_ref, _BIG)
        max_ref[...] = jnp.full_like(max_ref, -_BIG)

    bucket = bucket_ref[...]                                  # (1, R) int32
    owns = owns_ref[...] > 0                                  # (1, R) bool
    vals = vals_ref[...]                                      # (V, R) f32
    r = bucket.shape[1]
    b = n_buckets

    # one-hot (B, R): bucket membership of owned rows, built on the VPU.
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (b, r), 0)
    owned_f = ((bucket == iota_b) & owns).astype(jnp.float32)  # (B, R)

    # --- aggregate on the MXU (contract the row lanes) ---------------------
    nt = (((1,), (1,)), ((), ()))
    cnt_ref[0] = cnt_ref[0] + jnp.round(jax.lax.dot_general(
        jnp.ones((1, r), jnp.float32), owned_f, nt,
        precision=jax.lax.Precision.HIGHEST)).astype(jnp.int32)   # (1, B)
    sum_ref[0] = sum_ref[0] + jax.lax.dot_general(
        vals, owned_f, nt, precision=jax.lax.Precision.HIGHEST)   # (V, B)

    # --- min/max: one masked reduction per bucket the block touches ------
    # The stream is bucket-sorted, so a block's owned rows span a narrow
    # bucket range: loop over just that range (dynamic bounds) and meet
    # each bucket's (V, 1) extremes into its lane of the partial tables.
    first = jnp.min(jnp.where(owns, bucket, b))
    last = jnp.max(jnp.where(owns, bucket, -1))
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)

    @pl.loop(first, last + 1)
    def _mm(k):
        sel = owns & (bucket == k)                            # (1, R)
        hit = lane_b == k                                     # (1, B)
        vmin = jnp.min(jnp.where(sel, vals, _BIG), axis=1, keepdims=True)
        vmax = jnp.max(jnp.where(sel, vals, -_BIG), axis=1, keepdims=True)
        cur_min = min_ref[0]
        cur_max = max_ref[0]
        min_ref[0] = jnp.where(hit, jnp.minimum(cur_min, vmin), cur_min)
        max_ref[0] = jnp.where(hit, jnp.maximum(cur_max, vmax), cur_max)


def tree_merge(cnt, s, mn, mx):
    """Log-depth pairwise merge of per-partial aggregates over axis 0.

    cnt, s, mn, mx: (P, ...) arrays (count i32, the rest f32) of any
    matching trailing shape. The combine is associative
    (add / add / min / max), so the merge tree is exact for count/min/max
    and order-insensitive up to f32 rounding for sum.
    """
    while cnt.shape[0] > 1:
        p = cnt.shape[0]
        if p % 2:       # odd level: pad one identity partial
            cnt = jnp.concatenate([cnt, jnp.zeros_like(cnt[:1])])
            s = jnp.concatenate([s, jnp.zeros_like(s[:1])])
            mn = jnp.concatenate([mn, jnp.full_like(mn[:1], _BIG)])
            mx = jnp.concatenate([mx, jnp.full_like(mx[:1], -_BIG)])
        cnt = cnt[0::2] + cnt[1::2]
        s = s[0::2] + s[1::2]
        mn = jnp.minimum(mn[0::2], mn[1::2])
        mx = jnp.maximum(mx[0::2], mx[1::2])
    return cnt[0], s[0], mn[0], mx[0]


@functools.partial(jax.jit,
                   static_argnames=("n_buckets", "block_rows", "interpret"))
def group_aggregate(keys: jnp.ndarray, values_t: jnp.ndarray, *,
                    n_buckets: int = 1024,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: bool = True):
    """keys (N,) int32, values_t (V,N) f32 column-major; N % block_rows ==
    0, V % 8 == 0 (wrapper pads).

    Returns (bucket_keys (B,) i32, count (B,) i32, sum (B,V) f32,
             min (B,V) f32, max (B,V) f32, overflow_mask (N,) bool) —
    the same contract as kernels/ref.py:group_aggregate, field for field.
    """
    n = keys.shape[0]
    v = values_t.shape[0]
    assert n % block_rows == 0 and v % 8 == 0, (n, v)
    assert n_buckets & (n_buckets - 1) == 0, "n_buckets must be a power of 2"

    # --- sort by bucket + global first-claim ownership, the stream put in
    # bucket order (pure XLA) ----------------------------------------------
    with jax.named_scope("fv.bucket_sort"):
        bucket = ref.bucket_of(keys, n_buckets)
        order, sb = ref.sort_by_bucket(bucket, n_buckets)
        start, _end, nonempty = ref.segment_spans(sb, n_buckets)
        claimed = jnp.where(nonempty, keys[order[start]], _SENT)
        owns = keys == claimed[bucket]
        sv = ref.take_lanes(values_t, order)
        so = owns[order].astype(jnp.int32)

    # --- grid shape: P partials x G blocks each, P <= MAX_PARTIALS ---------
    nb_total = n // block_rows
    p = min(nb_total, MAX_PARTIALS)
    g = -(-nb_total // p)
    pad_rows = p * g * block_rows - n
    if pad_rows:
        # inert pad: owns=0 rows contribute to no bucket (bucket id is
        # irrelevant once the owned one-hot masks them out)
        sb = jnp.concatenate([sb, jnp.zeros((pad_rows,), sb.dtype)])
        sv = jnp.concatenate([sv, jnp.zeros((v, pad_rows), sv.dtype)], 1)
        so = jnp.concatenate([so, jnp.zeros((pad_rows,), so.dtype)])

    # --- block-local one-hot MXU aggregation over the sorted stream --------
    kern = functools.partial(_block_kernel, n_buckets)
    row = pl.BlockSpec((1, block_rows), lambda i, j, g=g: (0, i * g + j))
    table = pl.BlockSpec((1, v, n_buckets), lambda i, j: (i, 0, 0))
    cnt_p, sum_p, min_p, max_p = pl.pallas_call(
        kern,
        grid=(p, g),
        in_specs=[
            row, row,
            pl.BlockSpec((v, block_rows), lambda i, j, g=g: (0, i * g + j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n_buckets), lambda i, j: (i, 0, 0)),
            table, table, table,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, 1, n_buckets), jnp.int32),
            jax.ShapeDtypeStruct((p, v, n_buckets), jnp.float32),
            jax.ShapeDtypeStruct((p, v, n_buckets), jnp.float32),
            jax.ShapeDtypeStruct((p, v, n_buckets), jnp.float32),
        ],
        interpret=interpret,
    )(sb[None, :], so[None, :], sv)

    # --- tree merge of the partials ----------------------------------------
    cnt, s, mn, mx = tree_merge(cnt_p, sum_p, min_p, max_p)
    return claimed, cnt[0], s.T, mn.T, mx.T, ~owns
