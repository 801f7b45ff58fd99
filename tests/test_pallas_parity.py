"""Pallas interpret-mode parity: the hash_group / hash_join kernels run
with `interpret=True` against the kernels/ref.py oracles, field for field.

This chips at the PR 1 follow-up ("a TPU run to validate the Pallas
lowering"): everything except the Mosaic compile itself is validated here —
BlockSpec structure, the one-hot MXU formulation, the bucket-sorted
block-local partials and their tree merge (PR 4), and the pad/unpad glue in
kernels/ops.py. What remains TPU-only is code generation, not semantics.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import hash_group as hg
from repro.kernels import ref as kref


def _raw_group(keys, vals, nb):
    """The raw kernel on column-major values, padded to its 8-sublane
    contract; sum/min/max come back (B, V)."""
    v = vals.shape[1]
    vals_t = np.zeros((-(-v // 8) * 8, len(keys)), np.float32)
    vals_t[:v] = vals.T
    bk, cnt, s, mn, mx, ovf = hg.group_aggregate(
        jnp.asarray(keys), jnp.asarray(vals_t), n_buckets=nb, interpret=True)
    return bk, cnt, s[:, :v], mn[:, :v], mx[:, :v], ovf


@pytest.mark.parametrize("n,card,nb,v", [
    (256, 10, 64, 1), (1024, 300, 128, 2), (512, 512, 32, 3),
    (2048, 7, 1024, 2),
])
def test_hash_group_raw_fields_vs_ref(rng, n, card, nb, v):
    """Raw kernel outputs == ref oracle: claims, counts and overflow are
    bit-identical; float aggregates match to tree-merge rounding."""
    keys = rng.integers(-card, card, size=n).astype(np.int32)
    vals = rng.normal(size=(n, v)).astype(np.float32)
    bk, cnt, s, mn, mx, ovf = _raw_group(keys, vals, nb)
    r = kref.group_aggregate(jnp.asarray(keys), jnp.asarray(vals), nb)
    np.testing.assert_array_equal(np.asarray(bk),
                                  np.asarray(r["bucket_keys"]))
    np.testing.assert_array_equal(np.asarray(cnt),
                                  np.asarray(r["count"]))
    np.testing.assert_array_equal(np.asarray(ovf),
                                  np.asarray(r["overflow_mask"]))
    np.testing.assert_allclose(np.asarray(s), np.asarray(r["sum"]),
                               rtol=1e-4, atol=1e-4)
    # min/max of UNCLAIMED buckets carry the implementations' respective
    # identities (kernel 3.0e38 vs ref finfo.max) and are dropped by every
    # consumer; compare claimed buckets only
    claimed = np.asarray(cnt) > 0
    np.testing.assert_allclose(np.asarray(mn)[claimed],
                               np.asarray(r["min"])[claimed], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mx)[claimed],
                               np.asarray(r["max"])[claimed], rtol=1e-6)


def test_hash_group_integer_data_bit_identical(rng):
    """Integer-valued f32 data: every field, sums included, is exact."""
    keys = rng.integers(0, 100, size=1024).astype(np.int32)
    vals = rng.integers(-50, 50, size=(1024, 2)).astype(np.float32)
    bk, cnt, s, mn, mx, ovf = _raw_group(keys, vals, 128)
    r = kref.group_aggregate(jnp.asarray(keys), jnp.asarray(vals), 128)
    claimed = np.asarray(cnt) > 0
    np.testing.assert_array_equal(np.asarray(s), np.asarray(r["sum"]))
    np.testing.assert_array_equal(np.asarray(mn)[claimed],
                                  np.asarray(r["min"])[claimed])
    np.testing.assert_array_equal(np.asarray(mx)[claimed],
                                  np.asarray(r["max"])[claimed])


@pytest.mark.parametrize("blocks", [1, 2, 3, 5, 8])
def test_tree_merge_any_block_count(rng, blocks):
    """The log-depth pairwise merge handles odd levels via identity pads
    and equals a flat reduction for any partial count."""
    b, v = 16, 2
    cnt = rng.integers(0, 9, size=(blocks, b, 1)).astype(np.int32)
    s = rng.normal(size=(blocks, b, v)).astype(np.float32)
    mn = rng.normal(size=(blocks, b, v)).astype(np.float32)
    mx = rng.normal(size=(blocks, b, v)).astype(np.float32)
    tc, ts, tmn, tmx = hg.tree_merge(jnp.asarray(cnt), jnp.asarray(s),
                                     jnp.asarray(mn), jnp.asarray(mx))
    np.testing.assert_array_equal(np.asarray(tc), cnt.sum(0))
    np.testing.assert_allclose(np.asarray(ts), s.sum(0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tmn), mn.min(0))
    np.testing.assert_array_equal(np.asarray(tmx), mx.max(0))


def test_segment_spans_and_segmented_reduce(rng):
    """The shared sort-based segment helpers in ref.py (used by the XLA
    path, the Pallas prologue and the cluster group merge)."""
    ids = np.sort(rng.integers(0, 10, size=64)).astype(np.int32)
    start, end, nonempty = kref.segment_spans(jnp.asarray(ids), 12)
    for seg in range(12):
        where = np.nonzero(ids == seg)[0]
        assert bool(nonempty[seg]) == (len(where) > 0)
        if len(where):
            assert int(start[seg]) == where[0]
            assert int(end[seg]) == where[-1]
    vals = rng.normal(size=(64, 2)).astype(np.float32)
    flags = np.concatenate([[True], ids[1:] != ids[:-1]])
    s, mn, mx = kref.segmented_reduce(
        jnp.asarray(vals), jnp.asarray(vals), jnp.asarray(vals),
        jnp.asarray(flags))
    for seg in range(12):
        where = np.nonzero(ids == seg)[0]
        if not len(where):
            continue
        i = where[-1]
        np.testing.assert_allclose(np.asarray(s)[i], vals[where].sum(0),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(mn)[i], vals[where].min(0))
        np.testing.assert_array_equal(np.asarray(mx)[i], vals[where].max(0))


@pytest.mark.parametrize("n,k,v", [(256, 8, 1), (512, 40, 3), (256, 1, 2)])
def test_hash_join_raw_vs_ref(rng, n, k, v):
    probe = rng.integers(0, 64, size=n).astype(np.int32)
    bkeys = rng.permutation(64)[:k].astype(np.int32)
    bvals = rng.normal(size=(k, v)).astype(np.float32)
    # pad to the kernel's tile contract exactly as ops.py does
    from repro.kernels import ops as kops
    joined, hit = kops.hash_join(jnp.asarray(probe), jnp.asarray(bkeys),
                                 jnp.asarray(bvals), interpret=True)
    rj, rh = kref.hash_join(probe, bkeys, bvals)
    np.testing.assert_array_equal(np.asarray(hit), rh)
    np.testing.assert_allclose(np.asarray(joined), rj, rtol=1e-6)


def test_hash_join_empty_build(rng):
    """K=0 (an empty co-partitioned build shard): no probe row matches, on
    both the Pallas pad path and the XLA lowering."""
    from repro.kernels import ops as kops
    probe = rng.integers(0, 64, size=128).astype(np.int32)
    empty_k = jnp.zeros((0,), jnp.int32)
    empty_v = jnp.zeros((0, 2), jnp.float32)
    joined, hit = kops.hash_join(jnp.asarray(probe), empty_k, empty_v,
                                 interpret=True)
    assert not np.asarray(hit).any()
    assert not np.asarray(joined).any()
    joined, hit = kops.hash_join_xla(jnp.asarray(probe), empty_k, empty_v)
    assert not np.asarray(hit).any()
    assert np.asarray(joined).shape == (128, 2)
