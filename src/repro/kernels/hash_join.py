"""Small-table join kernel (the paper's stated future work, §Conclusions:
"performing joins against small tables in the memory by reading the small
table into the FPGA and matching the tuples read from memory against it").

TPU adaptation: the build side lives in VMEM across all grid steps (the
FPGA on-chip-table analogue); the probe stream is matched per block with a
one-hot key-equality matmul on the MXU. Probe keys ride the lanes and
build keys the sublanes, so every operand and result is lane-dense
(column-major values, one probe row per lane):

    M[j, i]  = (build_key_j == probe_key_i)          (VPU compare)
    joined^T = build_values^T @ M                     (MXU gather-by-match,
                                                       bit-exact halves)
    matched  = col_sum(M) > 0

Build keys must be unique (enforced by the ops.py wrapper): each probe row
matches at most one build row, so M is one-hot per probe and the matmul IS
the value gather. 16-bit key halves keep the f32 compare exact (same trick
as hash_group).

K = 0 (an empty co-partitioned build shard — a cluster node whose probe
partition's keys all miss) is handled by the ops.py wrapper: it
short-circuits to a no-match result rather than lowering a zero-row build
block, so the kernel itself always sees K >= 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.select_project import permute_bits

DEFAULT_BLOCK_ROWS = 256


def _kernel(probe_ref, bkey_ref, bval_ref, out_ref, hit_ref):
    pk = probe_ref[...]                                       # (1, R) i32
    bk = bkey_ref[...]                                        # (K, 1) i32
    bv = bval_ref[...]                                        # (V, K) f32

    m_f = (bk == pk).astype(jnp.float32)                      # (K, R)
    # gather-by-match as a bit-exact permutation matmul
    bits = jax.lax.bitcast_convert_type(bv, jnp.int32)
    out_ref[...] = jax.lax.bitcast_convert_type(
        permute_bits(bits, m_f, in_axis=0), jnp.float32)      # (V, R)
    hits = jnp.sum(m_f, axis=0, keepdims=True)                # (1, R)
    hit_ref[...] = (hits > 0.5).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def hash_join(probe_keys: jnp.ndarray, build_keys: jnp.ndarray,
              build_vals_t: jnp.ndarray, *,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: bool = True):
    """probe_keys (1,N) i32; build_keys (K,1) i32 (unique);
    build_vals_t (V,K) f32. N % block_rows == 0, K % 8 == V % 8 == 0
    (wrapper pads).

    Returns (joined_t (V,N) f32 — matched build values, 0 where no match;
             hit (1,N) i32 — 1 where the probe key exists in the build).
    """
    n = probe_keys.shape[1]
    v, k = build_vals_t.shape
    assert n % block_rows == 0
    return pl.pallas_call(
        _kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),       # build side: VMEM
            pl.BlockSpec((v, k), lambda i: (0, 0)),       # resident per step
        ],
        out_specs=[
            pl.BlockSpec((v, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((v, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(probe_keys, build_keys, build_vals_t)
