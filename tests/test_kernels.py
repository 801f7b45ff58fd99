"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles.

Every Pallas kernel runs in interpret=True on CPU (the TPU target is the
BlockSpec structure, validated here for semantics). assert_allclose against
ref.py per the spec.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref as kref


# ---------------------------------------------------------------------------
# select_project
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,a", [(64, 4), (100, 8), (1000, 8), (257, 3),
                                 (4096, 16), (1, 8), (513, 130)])
def test_select_project_shapes(rng, n, a):
    table = rng.normal(size=(n, a)).astype(np.float32)
    sel_ops = np.zeros(a, np.int32)
    sel_vals = np.zeros(a, np.float32)
    sel_ops[0] = kref.OP_LT
    sel_vals[0] = 0.3
    if a > 2:
        sel_ops[2] = kref.OP_GE
        sel_vals[2] = -0.5
    proj = np.zeros(a, np.float32)
    proj[: max(1, a // 2)] = 1
    packed, count = kops.select_project(
        jnp.asarray(table), jnp.asarray(sel_ops), jnp.asarray(sel_vals),
        jnp.asarray(proj))
    rp, rc = kref.select_project(
        jnp.asarray(table), jnp.asarray(sel_ops), jnp.asarray(sel_vals),
        jnp.asarray(proj))
    assert int(count) == int(rc)
    np.testing.assert_allclose(np.asarray(packed)[: int(count)],
                               np.asarray(rp)[: int(rc)], rtol=1e-6)


@pytest.mark.parametrize("op", list(kref.OP_SKIP + 1 + np.arange(6)))
def test_select_every_predicate_op(rng, op):
    n, a = 500, 8
    table = rng.normal(size=(n, a)).astype(np.float32)
    # force exact matches to exist for EQ/NE
    table[::7, 1] = 0.25
    sel_ops = np.zeros(a, np.int32)
    sel_vals = np.zeros(a, np.float32)
    sel_ops[1] = op
    sel_vals[1] = 0.25
    proj = np.ones(a, np.float32)
    packed, count = kops.select_project(
        jnp.asarray(table), jnp.asarray(sel_ops), jnp.asarray(sel_vals),
        jnp.asarray(proj))
    rp, rc = kref.select_project(
        jnp.asarray(table), jnp.asarray(sel_ops), jnp.asarray(sel_vals),
        jnp.asarray(proj))
    assert int(count) == int(rc)
    np.testing.assert_allclose(np.asarray(packed)[: int(count)],
                               np.asarray(rp)[: int(rc)], rtol=1e-6)


def test_select_project_all_and_none(rng):
    n, a = 300, 8
    table = rng.normal(size=(n, a)).astype(np.float32)
    proj = np.ones(a, np.float32)
    # none match
    ops_none = np.zeros(a, np.int32)
    vals = np.zeros(a, np.float32)
    ops_none[0] = kref.OP_GT
    vals[0] = 1e9
    _, count = kops.select_project(jnp.asarray(table), jnp.asarray(ops_none),
                                   jnp.asarray(vals), jnp.asarray(proj))
    assert int(count) == 0
    # all match
    ops_all = np.zeros(a, np.int32)
    _, count = kops.select_project(jnp.asarray(table), jnp.asarray(ops_all),
                                   jnp.asarray(vals), jnp.asarray(proj))
    assert int(count) == n


def test_select_project_stability(rng):
    """Survivors keep their original relative order (stable packing)."""
    n, a = 700, 4
    table = rng.normal(size=(n, a)).astype(np.float32)
    table[:, 3] = np.arange(n, dtype=np.float32)  # order tag (within 2^24)
    sel_ops = np.zeros(a, np.int32)
    sel_vals = np.zeros(a, np.float32)
    sel_ops[0] = kref.OP_GT
    proj = np.ones(a, np.float32)
    packed, count = kops.select_project(
        jnp.asarray(table), jnp.asarray(sel_ops), jnp.asarray(sel_vals),
        jnp.asarray(proj))
    tags = np.asarray(packed)[: int(count), 3]
    assert np.all(np.diff(tags) > 0), "pack must preserve row order"


# The kernel carries a write offset across its grid and DMAs each full block
# of survivors to the next output block, through a ring of blocks in flight
# (`select_project._RING`): the cases straddle blocks, outrun the ring, cut
# rows mid-block or at a block's edge, and pass the tail pad.
R = 256
CARRIED = {
    # name: (rows, words, threshold on word 0 or None for no predicate,
    #        words projected away, n_valid)
    "none": (700, 8, -1e9, (), None),
    "all": (12 * R + 1, 8, None, (), None),
    "all_whole_blocks": (9 * R, 8, None, (), None),
    "straddle": (13 * R + 100, 8, 0.7, (), None),
    "n_valid_mid_block": (1500, 8, 0.5, (), 3 * R + 232),
    "projection": (900, 8, 0.4, (0, 3, 5), None),
    "nan_inf_neg_zero": (800, 8, 0.6, (), None),
    "ids_column_17": (1200, 17, 0.3, (), None),
}


@pytest.mark.parametrize("name", sorted(CARRIED))
def test_select_project_carried_compaction(rng, name):
    """Global compaction in the kernel: survivors bit-identical to
    ref.select_project in lanes [0, count), every word past them zero."""
    n, a, thr, dropped, n_valid = CARRIED[name]
    table = rng.uniform(size=(n, a)).astype(np.float32)
    if name == "nan_inf_neg_zero":
        special = np.array([np.nan, np.inf, -np.inf, -0.0], np.float32)
        table[:, 1:] = rng.choice(special, size=(n, a - 1))
    sel_ops = np.zeros(a, np.int32)
    sel_vals = np.zeros(a, np.float32)
    if thr is not None:
        sel_ops[0] = kref.OP_LT
        sel_vals[0] = thr
    proj = np.ones(a, np.float32)
    proj[list(dropped)] = 0
    packed_t, count = kops.select_project_cols(
        jnp.asarray(table.T), jnp.asarray(sel_ops), jnp.asarray(sel_vals),
        jnp.asarray(proj), None if n_valid is None else jnp.int32(n_valid))
    live = n if n_valid is None else n_valid
    rp, rc = kref.select_project(
        jnp.asarray(table[:live]), jnp.asarray(sel_ops),
        jnp.asarray(sel_vals), jnp.asarray(proj))
    got = np.asarray(packed_t).view(np.int32)
    assert got.shape == (a, n)
    assert int(count) == int(rc)
    np.testing.assert_array_equal(got[:, :int(count)],
                                  np.asarray(rp)[:int(rc)].T.view(np.int32))
    assert not got[:, int(count):].any(), "words past count must be zero"


# ---------------------------------------------------------------------------
# hash_group
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,card,nb", [(256, 10, 64), (1000, 50, 256),
                                       (3000, 200, 512), (100, 5, 1024),
                                       (2048, 2000, 256)])
def test_group_aggregate_exact(rng, n, card, nb):
    keys = rng.integers(0, card, size=n).astype(np.int32)
    vals = rng.normal(size=(n, 3)).astype(np.float32)
    got = kops.group_aggregate_full(jnp.asarray(keys), jnp.asarray(vals),
                                    n_buckets=nb)
    exact = kref.group_aggregate_exact(keys, vals)
    assert set(got) == set(exact)
    for k in exact:
        c, s, mn, mx = got[k]
        ce, se, mne, mxe = exact[k]
        assert c == ce
        np.testing.assert_allclose(s, se, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(mn, mne, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(mx, mxe, rtol=1e-5, atol=1e-6)


def test_group_negative_and_large_keys(rng):
    keys = np.array([-5, -5, 3, 1 << 20, 3, -5, 0, 0], np.int32)
    vals = np.ones((8, 1), np.float32)
    got = kops.group_aggregate_full(jnp.asarray(keys), jnp.asarray(vals),
                                    n_buckets=64)
    exact = kref.group_aggregate_exact(keys, vals)
    assert set(got) == set(exact)
    for k in exact:
        assert got[k][0] == exact[k][0]


def test_distinct(rng):
    keys = rng.integers(0, 37, size=900).astype(np.int32)
    got = kops.distinct(jnp.asarray(keys), n_buckets=64)
    assert got == sorted(set(keys.tolist()))


def test_group_overflow_contract(rng):
    """With tiny bucket count, collisions overflow but the kernel+client
    merge is still exact (paper's cuckoo-overflow contract)."""
    keys = rng.integers(0, 500, size=2000).astype(np.int32)
    vals = rng.normal(size=(2000, 2)).astype(np.float32)
    got = kops.group_aggregate_full(jnp.asarray(keys), jnp.asarray(vals),
                                    n_buckets=64)  # 500 keys >> 64 buckets
    exact = kref.group_aggregate_exact(keys, vals)
    assert set(got) == set(exact)
    total_count = sum(v[0] for v in got.values())
    assert total_count == 2000


# ---------------------------------------------------------------------------
# ctr_crypt
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 63, 64, 1000, 32768, 99999])
def test_crypt_roundtrip_and_ref(rng, n):
    data = rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
    key = np.array([0xA5A5A5A5, 0x12345678], np.uint32)
    enc = kops.crypt(jnp.asarray(data), key, 7)
    dec = kops.crypt(enc, key, 7)
    np.testing.assert_array_equal(np.asarray(dec), data)
    ref = kref.ctr_crypt(jnp.asarray(data), jnp.asarray(key), 7)
    np.testing.assert_array_equal(np.asarray(enc), np.asarray(ref))


def test_crypt_key_and_nonce_sensitivity(rng):
    data = rng.integers(0, 1 << 32, size=512, dtype=np.uint32)
    k1 = np.array([1, 2], np.uint32)
    k2 = np.array([1, 3], np.uint32)
    e1 = np.asarray(kops.crypt(jnp.asarray(data), k1, 0))
    e2 = np.asarray(kops.crypt(jnp.asarray(data), k2, 0))
    e3 = np.asarray(kops.crypt(jnp.asarray(data), k1, 1))
    assert (e1 != e2).mean() > 0.9
    assert (e1 != e3).mean() > 0.9
    # keystream should look uniform: bit balance within 3 sigma
    bits = np.unpackbits((e1 ^ data).view(np.uint8))
    assert abs(bits.mean() - 0.5) < 3 / (2 * np.sqrt(bits.size))


# ---------------------------------------------------------------------------
# dfa_match
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pattern,cases", [
    ("abc", [b"abc", b"xabcx", b"ab", b"abd", b""]),
    ("ab+c", [b"abc", b"abbbbc", b"ac", b"abb", b"zabbcz"]),
    ("a|b", [b"ccc", b"cac", b"b", b"", b"xyz"]),
    ("(ab)*c", [b"c", b"ababc", b"abab", b"xc", b"abc"]),
    ("a.c", [b"abc", b"a_c", b"ac", b"axxc", b"zzaxczz"]),
    ("[0-9]+", [b"abc123", b"no digits", b"7", b"", b"x9"]),
])
def test_regex_vs_python(pattern, cases):
    import re as pyre
    from repro.core.regex import compile_regex
    from repro.core.table import string_table
    table, accept = compile_regex(pattern)
    ft, mat, lens = string_table("s", list(cases), 24)
    mask = kops.regex_match(jnp.asarray(mat), jnp.asarray(lens),
                            jnp.asarray(table), jnp.asarray(accept))
    expect = [bool(pyre.search(pattern.encode(), s)) for s in cases]
    assert np.asarray(mask).tolist() == expect


def test_regex_vs_ref_oracle(rng):
    from repro.core.regex import compile_regex
    table, accept = compile_regex("b[a-d]+a")
    n, width = 300, 20
    mat = rng.integers(97, 103, size=(n, width)).astype(np.uint8)
    lens = rng.integers(0, width + 1, size=n).astype(np.int32)
    got = kops.regex_match(jnp.asarray(mat), jnp.asarray(lens),
                           jnp.asarray(table), jnp.asarray(accept))
    ref = kref.dfa_match(jnp.asarray(mat), jnp.asarray(lens),
                         jnp.asarray(table), jnp.asarray(accept))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,d,s", [
    (1, 4, 4, 64, 256), (2, 8, 2, 64, 512), (3, 16, 16, 128, 300),
    (2, 8, 1, 128, 1024), (1, 32, 8, 96, 257),
])
def test_decode_attention_vs_ref(rng, b, hq, hkv, d, s):
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    k = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    lengths = rng.integers(1, s + 1, size=b).astype(np.int32)
    o, m, l = kops.decode_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(lengths))
    ro, rm, rl = kref.decode_attention(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), jnp.asarray(lengths))
    out = np.asarray(o) / np.maximum(np.asarray(l), 1e-30)[..., None]
    rout = np.asarray(ro) / np.maximum(np.asarray(rl), 1e-30)[..., None]
    np.testing.assert_allclose(out, rout, rtol=1e-4, atol=1e-5)


def test_decode_attention_bf16(rng):
    b, hq, hkv, d, s = 2, 8, 2, 64, 512
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    k = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    lengths = np.array([500, 31], np.int32)
    o, m, l = kops.decode_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), jnp.asarray(lengths))
    ro, rm, rl = kref.decode_attention(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), jnp.asarray(lengths))
    out = np.asarray(o) / np.maximum(np.asarray(l), 1e-30)[..., None]
    rout = np.asarray(ro) / np.maximum(np.asarray(rl), 1e-30)[..., None]
    np.testing.assert_allclose(out, rout, rtol=0.05, atol=0.05)


def test_partial_merge_equals_full(rng):
    """Sharded partials merged == full attention (the far-KV invariant)."""
    b, hq, hkv, d, s, shards = 2, 8, 2, 64, 1024, 4
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    k = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    lengths = np.array([1000, 700], np.int32)
    per = s // shards
    parts = []
    for i in range(shards):
        loc_len = np.clip(lengths - i * per, 0, per).astype(np.int32)
        parts.append(kops.decode_attention(
            jnp.asarray(q), jnp.asarray(k[:, i * per:(i + 1) * per]),
            jnp.asarray(v[:, i * per:(i + 1) * per]), jnp.asarray(loc_len)))
    merged = kref.merge_partials(parts)
    full = kref.full_attention_oracle(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=1e-4, atol=1e-5)
