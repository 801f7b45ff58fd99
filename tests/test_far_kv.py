"""Unit tests for the far-KV library (core/far_kv.py): the disaggregated
KV pool primitives used by the serving stack."""
import numpy as np
import jax.numpy as jnp
import pytest
from jax import shard_map

from repro.core import far_kv
from repro.launch.mesh import make_mesh
from repro.kernels import ref as kref


def test_partial_attention_matches_oracle(rng):
    b, hq, hkv, d, s = 2, 8, 2, 32, 128
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    lengths = jnp.asarray([100, 37], jnp.int32)
    o, m, l = far_kv.partial_attention(q, k, v, lengths, scale=d ** -0.5)
    full = o / jnp.maximum(l, 1e-30)[..., None]
    ref = kref.full_attention_oracle(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_partial_attention_bf16_cache(rng):
    """The MXU-native path: bf16 K/V, f32 accumulation, no f32 copies."""
    b, hq, hkv, d, s = 2, 4, 4, 32, 64
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k32 = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v32 = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    lengths = jnp.asarray([64, 20], jnp.int32)
    o, m, l = far_kv.partial_attention(
        q, k32.astype(jnp.bfloat16), v32.astype(jnp.bfloat16), lengths,
        scale=d ** -0.5)
    assert o.dtype == jnp.float32          # f32 accumulation preserved
    full = o / jnp.maximum(l, 1e-30)[..., None]
    ref = kref.full_attention_oracle(q, k32, v32, lengths)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref),
                               rtol=0.05, atol=0.05)


def test_shipped_bytes_model_monotonicity():
    """far is constant in S; naive grows linearly; local is smallest wire."""
    kw = dict(batch=8, hq=32, hkv=8, head_dim=128, tp=16)
    far_4k = far_kv.shipped_bytes_per_layer("far", seq_len=4096, **kw)
    far_500k = far_kv.shipped_bytes_per_layer("far", seq_len=524288, **kw)
    assert far_4k == far_500k              # push-down ships O(1) in S
    nai_4k = far_kv.shipped_bytes_per_layer("naive", seq_len=4096, **kw)
    nai_8k = far_kv.shipped_bytes_per_layer("naive", seq_len=8192, **kw)
    assert nai_8k > 1.9 * nai_4k           # fetch grows ~linearly in S
    assert nai_4k > far_4k                  # push-down always cheaper
    loc = far_kv.shipped_bytes_per_layer("local", seq_len=4096, **kw)
    assert loc < far_4k


def test_append_seq_sharded_semantics(rng):
    """append writes exactly the owning shard's row (simulated shards)."""
    # emulate 4 shards with vmap over an explicit axis using shard_map on
    # a 1-device mesh is overkill; test the index math directly
    b, s_loc, hkv, d = 2, 16, 2, 8
    import functools
    mesh = make_mesh((1,), ("model",))
    from jax.sharding import PartitionSpec as P
    k_cache = jnp.zeros((b, s_loc, hkv, d))
    v_cache = jnp.zeros((b, s_loc, hkv, d))
    k_new = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.float32)

    def run(pos):
        f = shard_map(
            functools.partial(far_kv.append_seq_sharded, axis="model"),
            mesh=mesh, in_specs=(P(), P(), P(), P(), P()),
            out_specs=(P(), P()), check_vma=False)
        return f(k_cache, v_cache, k_new, v_new, jnp.int32(pos))

    k2, v2 = run(5)
    np.testing.assert_allclose(np.asarray(k2[:, 5]), np.asarray(k_new),
                               rtol=1e-6)
    assert float(jnp.sum(jnp.abs(k2))) == pytest.approx(
        float(jnp.sum(jnp.abs(k_new))), rel=1e-5)   # only one row written
    # out-of-range pos writes nothing
    k3, v3 = run(99)
    assert float(jnp.sum(jnp.abs(k3))) == 0.0


def test_merge_partials_named_single_axis(rng):
    """pmax/psum merge on a 1-device axis reduces to plain normalize."""
    b, hq, d = 2, 4, 16
    o = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(b, hq)), jnp.float32)
    l = jnp.abs(jnp.asarray(rng.normal(size=(b, hq)), jnp.float32)) + 0.1
    mesh = make_mesh((1,), ("model",))
    from jax.sharding import PartitionSpec as P
    out = shard_map(
        lambda o, m, l: far_kv.merge_partials_named(o, m, l, "model"),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False)(o, m, l)
    ref = o / l[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
