"""Pure-jnp oracles for every Pallas kernel in repro.kernels.

Each function here is the semantic ground truth: slow, simple, obviously
correct. Kernel tests sweep shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Predicate op codes shared with the kernels (paper §5.3 predicate selection).
OP_SKIP, OP_LT, OP_LE, OP_GT, OP_GE, OP_EQ, OP_NE = range(7)

KEY_SENTINEL = np.iinfo(np.int32).min  # "empty bucket" marker (hash_group)


# ---------------------------------------------------------------------------
# select_project
# ---------------------------------------------------------------------------
def eval_predicate(table: jnp.ndarray, sel_ops: jnp.ndarray,
                   sel_vals: jnp.ndarray, *, col_axis: int = 1) -> jnp.ndarray:
    """AND-of-per-column-comparisons predicate.

    table: (N, A) float32/int32 columns — or (A, N) with col_axis=0.
    sel_ops: (A,) int32 op codes (OP_SKIP disables the column).
    sel_vals: (A,) same dtype as table, comparison constants.
    Returns (N,) bool mask.
    """
    shape = [1, 1]
    shape[col_axis] = -1
    col = table
    val = sel_vals.reshape(shape)
    ops = sel_ops.reshape(shape)
    per_col = jnp.where(
        ops == OP_LT, col < val,
        jnp.where(ops == OP_LE, col <= val,
                  jnp.where(ops == OP_GT, col > val,
                            jnp.where(ops == OP_GE, col >= val,
                                      jnp.where(ops == OP_EQ, col == val,
                                                jnp.where(ops == OP_NE, col != val,
                                                          True))))))
    return jnp.all(per_col, axis=col_axis)


def select_project(table: jnp.ndarray, sel_ops: jnp.ndarray,
                   sel_vals: jnp.ndarray, proj_mask: jnp.ndarray):
    """Filter rows by predicate, zero out non-projected columns, compact.

    Returns (packed, count): packed (N, A) with survivors (projected columns
    only; dropped columns zeroed) moved to the front in original order, tail
    zero-filled; count = number of survivors.
    """
    n = table.shape[0]
    mask = eval_predicate(table, sel_ops, sel_vals)
    projected = jnp.where(proj_mask[None, :].astype(bool), table, 0)
    # Stable compaction: survivors first, original order preserved.
    order = jnp.argsort(~mask, stable=True)
    packed = jnp.where(mask[order][:, None], projected[order], 0)
    return packed, jnp.sum(mask.astype(jnp.int32))


# ---------------------------------------------------------------------------
# hash_group (distinct / group-by / aggregation)
# ---------------------------------------------------------------------------
def bucket_of(keys: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Multiplicative (Fibonacci) hash of int32 keys into n_buckets (pow2)."""
    h = (keys.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
    shift = 32 - int(np.log2(n_buckets))
    return (h >> shift).astype(jnp.int32)


def take_lanes(x_t: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """x_t[:, idx] for a column-major (A, N) table, as one 1-D gather per
    row: a 2-D column gather under vmap (stacked rounds) takes the TPU
    compiler about 20x longer to compile."""
    return jnp.stack([jnp.take(x_t[c], idx, mode="clip")
                      for c in range(x_t.shape[0])])


def sort_by_bucket(bucket: jnp.ndarray, n_buckets: int):
    """Stable sort of rows by bucket id -> (order, sorted_buckets).

    Fast path: pack (bucket, row) into ONE uint32 composite key
    `bucket * N + row` and value-sort it — stability is by construction
    (rows of a bucket keep ascending index), and XLA's single-array
    primitive sort is ~5x faster on CPU than the comparator-pair sort
    argsort lowers to. Falls back to stable argsort when the composite
    would overflow 32 bits (n_buckets * N > 2^32).
    """
    n = bucket.shape[0]
    if n and n_buckets * n <= 2**32:
        comp = jnp.sort(bucket.astype(jnp.uint32) * jnp.uint32(n)
                        + jnp.arange(n, dtype=jnp.uint32))
        order = (comp % jnp.uint32(n)).astype(jnp.int32)
        return order, (comp // jnp.uint32(n)).astype(jnp.int32)
    order = jnp.argsort(bucket, stable=True)
    return order.astype(jnp.int32), bucket[order]


def segment_spans(sorted_seg_ids: jnp.ndarray, n_segments: int):
    """Per-segment [start, end] row spans of a bucket-sorted id array.

    sorted_seg_ids: (N,) int32, non-decreasing. Returns (start (S,), end (S,),
    nonempty (S,) bool) where end is the INCLUSIVE last row (clipped to a
    valid index; mask with `nonempty` before trusting it).
    """
    n = sorted_seg_ids.shape[0]
    seg = jnp.arange(n_segments, dtype=sorted_seg_ids.dtype)
    lo = jnp.searchsorted(sorted_seg_ids, seg, side="left")
    hi = jnp.searchsorted(sorted_seg_ids, seg, side="right")
    nonempty = hi > lo
    return (jnp.clip(lo, 0, max(n - 1, 0)).astype(jnp.int32),
            jnp.clip(hi - 1, 0, max(n - 1, 0)).astype(jnp.int32), nonempty)


def segmented_reduce(sums: jnp.ndarray, mins: jnp.ndarray, maxs: jnp.ndarray,
                     starts: jnp.ndarray, counts: jnp.ndarray | None = None):
    """Inclusive segmented scan of (sum, min, max[, count]) in one pass.

    sums/mins/maxs: (N, V); starts: (N,) bool segment-start flags over rows
    already sorted by segment; counts: optional (N,) int per-row weights
    scanned with the same flag-reset combine (the group-merge path needs
    exact int totals; group_aggregate uses a plain cumsum instead). Lowers
    to `jax.lax.associative_scan` — a log-depth data-parallel tree, never a
    serialized scatter. Row i of each output holds the running reduction
    since its segment's first row, so the segment totals sit at the segment
    END rows (gather via segment_spans). Returns (sum, min, max) or
    (count, sum, min, max) when counts is given.
    """
    f = starts[:, None]

    def comb(a, b):
        sa, mna, mxa, *ca, fa = a
        sb, mnb, mxb, *cb, fb = b
        out = (jnp.where(fb, sb, sa + sb),
               jnp.where(fb, mnb, jnp.minimum(mna, mnb)),
               jnp.where(fb, mxb, jnp.maximum(mxa, mxb)))
        if ca:
            out += (jnp.where(fb[:, 0], cb[0], ca[0] + cb[0]),)
        return out + (fa | fb,)

    if counts is None:
        s, mn, mx, _ = jax.lax.associative_scan(comb, (sums, mins, maxs, f))
        return s, mn, mx
    s, mn, mx, c, _ = jax.lax.associative_scan(
        comb, (sums, mins, maxs, counts, f))
    return c, s, mn, mx


def group_aggregate(keys: jnp.ndarray, values: jnp.ndarray, n_buckets: int):
    """Hash-grouped aggregation with first-claim buckets + overflow.

    keys: (N,) int32 (must be > KEY_SENTINEL). values: (N, V) float32.
    Bucket ownership: the first row (lowest index) hashing into a bucket
    claims it; later rows with a *different* key in the same bucket overflow
    (paper: cuckoo-collision rows are shipped to the client for software
    post-processing).

    Lowering: sort-based segment-reduce. Rows are stably sorted by bucket
    (composite-key value sort, `sort_by_bucket`), so each bucket is a
    contiguous segment whose FIRST row is the lowest-original-index row
    (the claimant); count comes from an exact int cumulative sum and
    sum/min/max from one segmented associative scan (log-depth tree) —
    all data-parallel primitives, replacing the `.at[].add/min/max`
    scatters that serialized on the host and capped cluster group
    scale-out (ROADMAP PR 3 follow-up).

    Returns dict with:
      bucket_keys (B,) int32 (KEY_SENTINEL if unclaimed)
      count (B,) int32 ; sum/min/max (B, V) float32 (claimed keys only)
      overflow_mask (N,) bool — rows that must be re-aggregated client-side
    """
    n, v = values.shape
    b = bucket_of(keys, n_buckets)
    order, sb = sort_by_bucket(b, n_buckets)
    start, end, nonempty = segment_spans(sb, n_buckets)
    # first-claim: after the stable sort, each segment's first row is the
    # bucket's lowest-original-index row
    claimed = jnp.where(nonempty, keys[order[start]], KEY_SENTINEL)
    owns = keys == claimed[b]
    ovf = ~owns
    so = owns[order]
    sv = values[order]
    # count: exact int32 prefix-sum difference over owned rows
    oc = so.astype(jnp.int32)
    csum = jnp.cumsum(oc)
    count = jnp.where(nonempty, csum[end] - (csum[start] - oc[start]), 0)
    # sum/min/max: one segmented scan; non-owned rows carry the identity
    big = jnp.asarray(jnp.finfo(values.dtype).max, values.dtype)
    flags = jnp.concatenate([jnp.ones((min(n, 1),), bool), sb[1:] != sb[:-1]])
    ssum, smin, smax = segmented_reduce(
        jnp.where(so[:, None], sv, 0), jnp.where(so[:, None], sv, big),
        jnp.where(so[:, None], sv, -big), flags)
    ne = nonempty[:, None]
    s = jnp.where(ne, ssum[end], 0)
    mn = jnp.where(ne, smin[end], big)
    mx = jnp.where(ne, smax[end], -big)
    return dict(bucket_keys=claimed, count=count, sum=s, min=mn, max=mx,
                overflow_mask=ovf)


def group_aggregate_exact(keys: np.ndarray, values: np.ndarray):
    """Dict-based exact group-by (numpy) — oracle for kernel+client-side merge."""
    out: dict[int, list] = {}
    for k, row in zip(np.asarray(keys).tolist(), np.asarray(values)):
        e = out.setdefault(k, [0, np.zeros_like(row, dtype=np.float64),
                               np.full_like(row, np.inf, dtype=np.float64),
                               np.full_like(row, -np.inf, dtype=np.float64)])
        e[0] += 1
        e[1] = e[1] + row
        e[2] = np.minimum(e[2], row)
        e[3] = np.maximum(e[3], row)
    return out


# ---------------------------------------------------------------------------
# dfa_match (regex)
# ---------------------------------------------------------------------------
def dfa_match(strings: jnp.ndarray, lengths: jnp.ndarray,
              table: jnp.ndarray, accept: jnp.ndarray) -> jnp.ndarray:
    """Run a DFA over each row of byte-strings.

    strings: (R, L) uint8 (0-padded). lengths: (R,) int32.
    table: (S, 256) int32 transition table. accept: (S,) bool.
    Semantics: start in state 0, consume chars [0, len); accept iff the state
    after the last consumed char is accepting. (Search semantics come from the
    DFA itself being built for `.*R` with absorbing accept states.)
    """
    r, l = strings.shape

    def step(state, t):
        ch = strings[:, t].astype(jnp.int32)
        nxt = table[state, ch]
        state = jnp.where(t < lengths, nxt, state)
        return state, None

    state0 = jnp.zeros((r,), jnp.int32)
    state, _ = jax.lax.scan(step, state0, jnp.arange(l))
    return accept[state]


# ---------------------------------------------------------------------------
# ctr_crypt (ARX counter-mode cipher, Threefry-2x32 schedule)
# ---------------------------------------------------------------------------
_ROTS = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(key: jnp.ndarray, c0: jnp.ndarray, c1: jnp.ndarray):
    """Threefry-2x32, 20 rounds. key: (2,) uint32; c0/c1: uint32 arrays."""
    k0, k1 = key[0], key[1]
    k2 = k0 ^ k1 ^ _PARITY
    ks = [k0, k1, k2]
    x0 = c0 + ks[0]
    x1 = c1 + ks[1]
    for block in range(5):
        for r in range(4):
            x0 = x0 + x1
            x1 = _rotl(x1, _ROTS[(4 * block + r) % 8])
            x1 = x0 ^ x1
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def ctr_crypt(data: jnp.ndarray, key: jnp.ndarray, nonce: int,
              idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """XOR data (N,) uint32 with the Threefry CTR keystream. Involutive.

    The keystream is positional: word i is XORed with stream position
    `idx[i]` (default arange(N) — a contiguous buffer starting at stream
    position 0). Passing explicit positions lets a partition of a larger
    buffer decrypt with the offsets it had inside the original flattening
    (the multi-node scatter path: each node holds a row subset of one
    encrypted table).
    """
    n = data.shape[0]
    idx = (jnp.arange(n, dtype=jnp.uint32) if idx is None
           else idx.astype(jnp.uint32))
    blk = idx >> 1  # each threefry call yields 2 words
    lane = idx & 1
    s0, s1 = threefry2x32(key, blk, jnp.full_like(blk, np.uint32(nonce)))
    stream = jnp.where(lane == 0, s0, s1)
    return data ^ stream


# ---------------------------------------------------------------------------
# decode_attention (far-KV partial flash attention)
# ---------------------------------------------------------------------------
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, scale: float | None = None):
    """Single-token GQA attention over a KV shard, returning merge partials.

    q: (B, Hq, D); k/v: (B, S, Hkv, D); lengths: (B,) valid KV rows.
    Returns (o, m, l): o (B, Hq, D) un-normalized (o = sum softmax-weights*V
    scaled by exp(-m) convention: o = sum(exp(s - m) v)), m (B, Hq) running
    max, l (B, Hq) sum(exp(s - m)). Full attention = o / l after cross-shard
    merge. All math in f32.
    """
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, kf) * scale
    pos = jnp.arange(s)[None, None, None, :]
    valid = pos < lengths[:, None, None, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)
    msafe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(valid, jnp.exp(scores - msafe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, vf)
    return (o.reshape(b, hq, d), msafe.reshape(b, hq), l.reshape(b, hq))


def merge_partials(parts):
    """Merge per-shard (o, m, l) partials into final attention output.

    parts: list of (o, m, l). Returns normalized (B, Hq, D) f32 output.
    """
    os = jnp.stack([p[0] for p in parts])
    ms = jnp.stack([p[1] for p in parts])
    ls = jnp.stack([p[2] for p in parts])
    m = jnp.max(ms, axis=0)
    w = jnp.exp(ms - m[None])
    l = jnp.sum(ls * w, axis=0)
    o = jnp.sum(os * w[..., None], axis=0)
    return o / jnp.maximum(l, 1e-30)[..., None]


def full_attention_oracle(q, k, v, lengths, scale=None):
    """Plain masked softmax attention for testing partial merges."""
    o, m, l = decode_attention(q, k, v, lengths, scale)
    return o / jnp.maximum(l, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# hash_join (small-table inner join; the paper's stated future work)
# ---------------------------------------------------------------------------
def hash_join(probe_keys, build_keys, build_vals):
    """Oracle: dict-based unique-key inner join.

    probe_keys (N,) i32; build_keys (K,) i32 unique; build_vals (K, V) f32.
    Returns (joined (N, V) — matched build row or zeros, hit (N,) bool).
    """
    lut = {int(k): i for i, k in enumerate(np.asarray(build_keys))}
    n = len(probe_keys)
    v = np.asarray(build_vals).shape[1]
    joined = np.zeros((n, v), np.float32)
    hit = np.zeros((n,), bool)
    for i, k in enumerate(np.asarray(probe_keys)):
        j = lut.get(int(k))
        if j is not None:
            joined[i] = np.asarray(build_vals)[j]
            hit[i] = True
    return joined, hit
