"""Public jit'd wrappers around the Pallas kernels.

Handles: padding to tile boundaries, layout transforms (transposes, halves),
platform auto-detection (interpret=True off-TPU), and result un-padding.
These are the entry points the core/ layer and the benchmarks call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (ctr_crypt as _ctr, decode_attention as _dec,
                           dfa_match as _dfa, hash_group as _hg,
                           hash_join as _hj, ref,
                           select_project as _sp)


@functools.cache
def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# selection / projection
# ---------------------------------------------------------------------------
def select_project(table, sel_ops, sel_vals, proj_mask, *,
                   block_rows: int = 256, interpret: bool | None = None):
    """table (N, A) f32; sel_ops (A,) i32; sel_vals/proj_mask (A,) f32.

    Returns (packed (N, A) f32 globally compacted, count scalar i32).
    """
    packed_t, total = select_project_cols(
        jnp.asarray(table).T, sel_ops, sel_vals, proj_mask,
        block_rows=block_rows, interpret=interpret)
    return packed_t.T, total


def select_project_cols(table_t, sel_ops, sel_vals, proj_mask, n_valid=None,
                        *, block_rows: int = 256,
                        interpret: bool | None = None):
    """Column-major select_project: table_t (A, N) f32, one row per lane.

    Rows at or past `n_valid` (optional traced scalar) never survive.
    Returns (packed_t (A, N) f32 globally compacted, count scalar i32).
    """
    if interpret is None:
        interpret = _interpret_default()
    a, n = table_t.shape
    if n == 0:
        return jnp.zeros((a, 0), jnp.float32), jnp.int32(0)
    t = _pad_to(_pad_to(table_t.astype(jnp.float32), 0, 8), 1, block_rows)
    # padded columns must not affect the predicate: pad ops with OP_SKIP
    ops2 = _pad_to(jnp.asarray(sel_ops, jnp.int32)[:, None], 0, 8,
                   value=ref.OP_SKIP)
    vals2 = _pad_to(jnp.asarray(sel_vals, jnp.float32)[:, None], 0, 8)
    proj2 = _pad_to(jnp.asarray(proj_mask, jnp.float32)[:, None], 0, 8)
    limit = (jnp.int32(n) if n_valid is None
             else jnp.minimum(jnp.asarray(n_valid, jnp.int32), n))
    packed_b, counts = _sp.select_project(t, ops2, vals2, proj2,
                                          limit.reshape(1, 1),
                                          block_rows=block_rows,
                                          interpret=interpret)
    # --- stitch blocks (the paper's length-prefixed response packets) ------
    # output row p comes from the first block whose running end exceeds p
    ends = jnp.cumsum(counts)
    total = ends[-1]
    p = jnp.arange(n, dtype=jnp.int32)
    blk = jnp.minimum(jnp.searchsorted(ends, p, side="right"),
                      counts.shape[0] - 1).astype(jnp.int32)
    src = blk * block_rows + p - (ends[blk] - counts[blk])
    return jnp.where(p < total, ref.take_lanes(packed_b[:a], src), 0.0), total


# ---------------------------------------------------------------------------
# grouping
# ---------------------------------------------------------------------------
def group_aggregate(keys, values, *, n_buckets: int = 1024,
                    block_rows: int = 256, interpret: bool | None = None):
    """keys (N,) int32, values (N, V) f32 -> dict of aggregates + overflow.

    Overflow rows (bucket collisions) are returned for client-side merge,
    mirroring the paper's cuckoo-overflow contract.
    """
    return group_aggregate_cols(keys, jnp.asarray(values).T,
                                n_buckets=n_buckets, block_rows=block_rows,
                                interpret=interpret)


def group_aggregate_cols(keys, values_t, *, n_buckets: int = 1024,
                         block_rows: int = 256,
                         interpret: bool | None = None):
    """`group_aggregate` over column-major values_t (V, N): lane-dense, for
    the fused pipeline's column layout. Same result dict."""
    if interpret is None:
        interpret = _interpret_default()
    n = keys.shape[0]
    v = values_t.shape[0]
    kp = _pad_to(jnp.asarray(keys, jnp.int32), 0, block_rows,
                 value=ref.KEY_SENTINEL + 1)  # sentinel+1: a real-ish key
    vp = _pad_to(_pad_to(jnp.asarray(values_t, jnp.float32), 1, block_rows),
                 0, 8)
    bkey, cnt, s, mn, mx, ovf = _hg.group_aggregate(
        kp, vp, n_buckets=n_buckets,
        block_rows=block_rows, interpret=interpret)
    # Remove padded rows' contribution: padded rows all carry the same key
    # (KEY_SENTINEL+1); subtract them exactly.
    npad = kp.shape[0] - n
    if npad:
        pad_key = jnp.int32(ref.KEY_SENTINEL + 1)
        pb = ref.bucket_of(pad_key[None], n_buckets)[0]
        owned_pad = bkey[pb] == pad_key
        # they contributed `npad` count and zero sums (values padded w/ 0)
        cnt = cnt.at[pb].add(jnp.where(owned_pad, -npad, 0))
        empty_now = owned_pad & (cnt[pb] == 0)
        bkey = bkey.at[pb].set(jnp.where(empty_now, ref.KEY_SENTINEL,
                                         bkey[pb]))
        # min/max may be polluted by pad zeros when the pad key owns pb; that
        # bucket is dropped client-side if empty; if the pad key collided
        # with a real key, pads are overflow rows (handled below).
    return dict(bucket_keys=bkey, count=cnt, sum=s[:, :v],
                min=mn[:, :v], max=mx[:, :v], overflow_mask=ovf[:n])


def group_aggregate_full(keys, values, *, n_buckets: int = 1024,
                         block_rows: int = 256,
                         interpret: bool | None = None):
    """Kernel aggregation + client-side overflow merge -> exact dict result.

    This is the end-to-end paper contract: the smart memory aggregates what
    fits its hash table; collision overflow is merged in "client software".
    Returns {key: (count, sum, min, max)} over *all* keys.
    """
    res = group_aggregate(keys, values, n_buckets=n_buckets,
                          block_rows=block_rows, interpret=interpret)
    return _finalize_group_full(keys, values, res)


def _finalize_group_full(keys, values, res):
    """Finalize boundary: sync the kernel's lazy bucket outputs to the host
    and merge collision overflow in "client software" (the paper's split).
    The only host transfer in the group path lives here."""
    out: dict[int, tuple] = {}
    bkeys = np.asarray(res["bucket_keys"])
    cnts = np.asarray(res["count"])
    sums = np.asarray(res["sum"])
    mins = np.asarray(res["min"])
    maxs = np.asarray(res["max"])
    for i in range(bkeys.shape[0]):
        if bkeys[i] != ref.KEY_SENTINEL and cnts[i] > 0:
            out[int(bkeys[i])] = (int(cnts[i]), sums[i].copy(),
                                  mins[i].copy(), maxs[i].copy())
    ovf = np.asarray(res["overflow_mask"])
    kh = np.asarray(keys)[ovf]
    vh = np.asarray(values)[ovf]
    for k, row in zip(kh.tolist(), vh):
        if k in out:
            c, s, mn, mx = out[k]
            out[k] = (c + 1, s + row, np.minimum(mn, row),
                      np.maximum(mx, row))
        else:
            out[k] = (1, row.astype(np.float32).copy(), row.copy(),
                      row.copy())
    return out


def distinct(keys, *, n_buckets: int = 1024, block_rows: int = 256,
             interpret: bool | None = None):
    """DISTINCT via group_aggregate (count-only) + client-side overflow dedup."""
    vals = jnp.zeros((keys.shape[0], 1), jnp.float32)
    res = group_aggregate(keys, vals, n_buckets=n_buckets,
                          block_rows=block_rows, interpret=interpret)
    return _finalize_distinct(keys, res)


def _finalize_distinct(keys, res):
    """Finalize boundary: host-side dedup of bucket keys + overflow rows."""
    bk = np.asarray(res["bucket_keys"])
    cnt = np.asarray(res["count"])
    found = set(bk[(bk != ref.KEY_SENTINEL) & (cnt > 0)].tolist())
    ovf_keys = np.asarray(keys)[np.asarray(res["overflow_mask"])]
    found.update(ovf_keys.tolist())
    return sorted(found)


# ---------------------------------------------------------------------------
# regex
# ---------------------------------------------------------------------------
def regex_match(strings, lengths, table, accept, *,
                block_rows: int = 128, interpret: bool | None = None):
    """strings (N, L) uint8/int32; lengths (N,) i32; table (S, 256) i32;
    accept (S,) bool. Returns (N,) bool match mask."""
    if interpret is None:
        interpret = _interpret_default()
    n, l = strings.shape
    chars_t = _pad_to(strings.astype(jnp.int32).T, 1, block_rows)
    lens = _pad_to(lengths.astype(jnp.int32)[None, :], 1, block_rows)
    s = table.shape[0]
    table_t = table.astype(jnp.float32).T                     # (256, S)
    acc = accept.astype(jnp.float32)[None, :]                 # (1, S)
    out = _dfa.dfa_match(chars_t, lens, table_t, acc,
                         block_rows=block_rows, interpret=interpret)
    return out[:n].astype(bool)


# ---------------------------------------------------------------------------
# encryption
# ---------------------------------------------------------------------------
def crypt(data_u32, key2_u32, nonce: int, *, interpret: bool | None = None):
    """data (N,) uint32; key (2,) uint32; involutive CTR cipher."""
    if interpret is None:
        interpret = _interpret_default()
    n = data_u32.shape[0]
    cols = 128
    x = _pad_to(data_u32.astype(jnp.uint32)[None, :], 1, 256 * cols)
    x = x.reshape(-1, cols)
    key = jnp.array([[int(key2_u32[0]), int(key2_u32[1]), nonce & 0xFFFFFFFF,
                      0]], dtype=jnp.uint32)
    y = _ctr.ctr_crypt(x, key, interpret=interpret)
    return y.reshape(-1)[:n]


def crypt_cols(data_t_u32, key2_u32, nonce: int, *,
               interpret: bool | None = None):
    """`crypt` of a column-major table: data_t (A, N) uint32, word c of row
    r at [c, r] — the same keystream as `crypt` of the (N, A) row-major
    flattening, without materializing it."""
    if interpret is None:
        interpret = _interpret_default()
    n = data_t_u32.shape[1]
    x = _pad_to(jnp.asarray(data_t_u32, jnp.uint32), 1, _ctr.DEFAULT_COL_BLOCK)
    key = jnp.array([[int(key2_u32[0]), int(key2_u32[1]), nonce & 0xFFFFFFFF,
                      0]], dtype=jnp.uint32)
    return _ctr.ctr_crypt_cols(x, key, interpret=interpret)[:, :n]


# ---------------------------------------------------------------------------
# far-KV decode attention
# ---------------------------------------------------------------------------
def decode_attention(q, k, v, lengths, *, scale: float | None = None,
                     block_kv: int = 256, interpret: bool | None = None):
    """q (B, Hq, D); k/v (B, S, Hkv, D); lengths (B,).

    Returns unnormalized partials (o (B,Hq,D) f32, m (B,Hq), l (B,Hq)) for
    cross-shard merging with ref.merge_partials.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    gp = max(8, g)
    dp = ((d + 127) // 128) * 128
    sp = ((s + block_kv - 1) // block_kv) * block_kv
    qk = jnp.zeros((b, hkv, gp, dp), q.dtype)
    qk = qk.at[:, :, :g, :d].set(q.reshape(b, hkv, g, d))
    kt = jnp.zeros((b, hkv, sp, dp), k.dtype)
    kt = kt.at[:, :, :s, :d].set(jnp.swapaxes(k, 1, 2))
    vt = jnp.zeros((b, hkv, sp, dp), v.dtype)
    vt = vt.at[:, :, :s, :d].set(jnp.swapaxes(v, 1, 2))
    lens = lengths.astype(jnp.int32)[:, None]
    o, m, l = _dec.decode_attention(qk, kt, vt, lens, scale=float(scale),
                                    block_kv=block_kv, interpret=interpret)
    o = o[:, :, :g, :d].reshape(b, hq, d)
    m = m[:, :, :g, 0].reshape(b, hq)
    l = l[:, :, :g, 0].reshape(b, hq)
    return o, m, l


# ---------------------------------------------------------------------------
# small-table join
# ---------------------------------------------------------------------------
def hash_join(probe_keys, build_keys, build_vals, *, block_rows: int = 256,
              interpret: bool | None = None):
    """probe_keys (N,) i32; build_keys (K,) i32 UNIQUE; build_vals (K,V) f32.

    Inner join against a small build table resident in VMEM (paper
    §Conclusions future work). Returns (joined (N, V), hit (N,) bool).
    """
    joined_t, hit = hash_join_cols(probe_keys, build_keys, build_vals,
                                   block_rows=block_rows, interpret=interpret)
    return joined_t.T, hit


def hash_join_cols(probe_keys, build_keys, build_vals, *,
                   block_rows: int = 256, interpret: bool | None = None):
    """`hash_join` with the joined values column-major: (joined_t (V, N),
    hit (N,) bool) — lane-dense, for the fused pipeline's column layout."""
    if interpret is None:
        interpret = _interpret_default()
    if not isinstance(build_keys, jax.core.Tracer):
        bk = np.asarray(build_keys)
        if len(np.unique(bk)) != len(bk):
            raise ValueError(
                "build keys must be unique for a small-table join")
    n = probe_keys.shape[0]
    k, v = build_vals.shape
    if k == 0:      # empty co-partitioned build shard: nothing matches
        return (jnp.zeros((v, n), jnp.float32), jnp.zeros((n,), bool))
    pk = _pad_to(jnp.asarray(probe_keys, jnp.int32)[None, :], 1, block_rows,
                 value=ref.KEY_SENTINEL)        # sentinel never matches
    bkp = _pad_to(jnp.asarray(build_keys, jnp.int32)[:, None], 0, 8,
                  value=ref.KEY_SENTINEL + 1)   # distinct pad key
    bvp = _pad_to(_pad_to(jnp.asarray(build_vals, jnp.float32).T, 0, 8), 1, 8)
    joined_t, hit = _hj.hash_join(pk, bkp, bvp, block_rows=block_rows,
                                  interpret=interpret)
    return joined_t[:v, :n], hit[0, :n].astype(bool)


# ---------------------------------------------------------------------------
# XLA-native lowerings (fused request path off-TPU)
# ---------------------------------------------------------------------------
# The fused pipeline executable (core/pipeline.py) uses these when the
# Pallas kernels would run in interpret mode: same operator contracts as the
# kernels above (asserted against kernels/ref.py by tests/test_fused_path.py)
# but lowered to plain XLA ops, which on CPU are ~50x faster than emulating
# the MXU datapath. No tile padding or layout transforms are needed, so the
# traced program stays glue-free.

def select_project_xla(table, sel_ops, sel_vals, proj_mask, valid=None):
    """ref.select_project semantics + an optional row-validity mask.

    table (N, A) f32; sel_ops (A,) i32; sel_vals/proj_mask (A,) f32;
    valid (N,) bool or None. Returns (packed (N, A), count scalar i32):
    surviving valid rows stably compacted to the front, dropped columns
    zeroed, tail zero-filled.
    """
    mask = ref.eval_predicate(table, jnp.asarray(sel_ops),
                              jnp.asarray(sel_vals))
    if valid is not None:
        mask = mask & valid
    projected = jnp.where(jnp.asarray(proj_mask)[None, :].astype(bool),
                          table, 0)
    order = jnp.argsort(~mask, stable=True)
    packed = jnp.where(mask[order][:, None], projected[order], 0)
    return packed, jnp.sum(mask.astype(jnp.int32))


def hash_join_xla(probe_keys, build_keys, build_vals):
    """kernels.hash_join contract via sorted lookup (no VMEM hash table).

    probe_keys (N,) i32; build_keys (K,) i32 unique; build_vals (K, V) f32.
    Returns (joined (N, V) — matched build row or zeros, hit (N,) bool).
    K may be 0 (an empty co-partitioned build shard): nothing matches.
    """
    if build_keys.shape[0] == 0:
        n = probe_keys.shape[0]
        return (jnp.zeros((n, build_vals.shape[1]), jnp.float32),
                jnp.zeros((n,), bool))
    order = jnp.argsort(build_keys)
    sk = build_keys[order]
    sv = build_vals[order]
    idx = jnp.clip(jnp.searchsorted(sk, probe_keys), 0, sk.shape[0] - 1)
    hit = sk[idx] == probe_keys
    joined = jnp.where(hit[:, None], sv[idx], 0.0)
    return joined.astype(jnp.float32), hit
