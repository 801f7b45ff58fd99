"""Streaming selection + projection + packing kernel (paper §5.2-5.3, §5.5).

TPU adaptation of Farview's bump-in-the-wire filter pipeline:
  * the pallas grid streams row blocks HBM->VMEM (the AXI-stream analogue),
  * the table is held column-major, (C, N): one row per LANE, one column
    per sublane. An (N, C) row layout would be padded to 128 lanes in HBM
    — 16x for the paper's 8-word rows — while (C, N) is dense,
  * the predicate is evaluated on the VPU over the whole block at once
    (Farview's "vectorized model": lanes = parallel selection engines),
  * compaction ("packing") is a permutation *matmul* on the MXU: survivors
    are moved to the front of the block with rows @ P^T where
    P[i, j] = (prefix_sum(mask)[j]-1 == i) & mask[j]; the prefix sum is a
    matmul with an upper-triangular ones matrix, and the rows move as
    16-bit halves of their bit patterns, so the copy is bit-exact,
  * per-block survivor counts are emitted alongside — these are the
    length-prefixed RDMA response packets of the paper's sender unit.

Blocks are (C, rows=256) f32 tiles: C a multiple of the 8-sublane f32 tile,
rows a multiple of the 128-lane width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

DEFAULT_BLOCK_ROWS = 256
_CMPS = ((ref.OP_LT, jnp.less), (ref.OP_LE, jnp.less_equal),
         (ref.OP_GT, jnp.greater), (ref.OP_GE, jnp.greater_equal),
         (ref.OP_EQ, jnp.equal), (ref.OP_NE, jnp.not_equal))


def _kernel(limit_ref, table_ref, ops_ref, vals_ref, proj_ref,
            packed_ref, count_ref):
    cols = table_ref[...]                                    # (C, R) f32
    ops = ops_ref[...]                                       # (C, 1) i32
    vals = vals_ref[...]                                     # (C, 1) f32
    proj = proj_ref[...]                                     # (C, 1) f32
    f32 = jnp.float32
    r = cols.shape[1]

    # --- predicate (VPU) ---------------------------------------------------
    # 1.0 where a column's predicate holds (OP_SKIP always holds); selects
    # over f32, since Mosaic cannot select between i1 vectors
    ok = jnp.ones(cols.shape, f32)
    for code, cmp in _CMPS:
        ok = jnp.where(ops == code, cmp(cols, vals).astype(f32), ok)
    # rows at or past `limit` (tail padding, masked n_valid rows) never match
    row = pl.program_id(0) * r + jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    mask = (jnp.min(ok, axis=0, keepdims=True) > 0.5) & (row < limit_ref[0, 0])
    mask_f = mask.astype(f32)                                # (1, R)

    # --- projection (annotate columns, paper's projection_flags) -----------
    bits = jnp.where(proj > 0.5,                             # zero dropped cols
                     jax.lax.bitcast_convert_type(cols, jnp.int32), 0)

    # --- packing: compaction as a permutation matmul (MXU) ------------------
    k_i = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
    j_i = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    upper = (k_i <= j_i).astype(f32)
    pos = jax.lax.dot(mask_f, upper,
                      precision=jax.lax.Precision.HIGHEST) - 1.0  # (1, R)
    perm = ((pos == k_i.astype(f32)) & mask).astype(f32)     # (R out, R in)
    packed_ref[...] = jax.lax.bitcast_convert_type(
        permute_bits(bits, perm), packed_ref.dtype)
    # one lane-dense (8, 128) tile per block, every element the count: a
    # (1, 1) block would break the TPU's (8, 128) block tiling rule
    count_ref[...] = jnp.full(count_ref.shape, jnp.sum(mask_f),
                              f32).astype(jnp.int32)


def permute_bits(bits, perm, in_axis: int = 1):
    """Move 32-bit words (C, R in) through a 0/1 matrix perm — (R out, R in),
    or (R in, R out) with in_axis=0 — on the MXU, bit-exactly: each 16-bit
    half is an f32-exact integer, so NaN, inf and -0.0 pass unchanged and
    never leak into other rows (a float matmul would turn 0 * inf into
    NaN). Returns int32 (C, R out)."""
    def move(half):
        return jnp.round(jax.lax.dot_general(
            half.astype(jnp.float32), perm, (((1,), (in_axis,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)).astype(jnp.int32)
    hi = move(jax.lax.shift_right_logical(bits, 16))
    lo = move(bits & 0xFFFF)
    return jax.lax.shift_left(hi, 16) | lo


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def select_project(table_t: jnp.ndarray, sel_ops: jnp.ndarray,
                   sel_vals: jnp.ndarray, proj_mask: jnp.ndarray,
                   limit: jnp.ndarray, *,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = True):
    """Per-block packed survivors + per-block counts, column-major.

    table_t: (C, N) f32, C % 8 == 0, N % block_rows == 0 (wrapper pads).
    sel_ops: (C, 1) int32 opcodes; sel_vals/proj_mask: (C, 1) f32.
    limit: (1, 1) int32 — rows >= limit never survive.
    Returns: packed (C, N) f32 (block-local compaction), counts (nb,) i32.
    """
    c, n = table_t.shape
    assert n % block_rows == 0 and c % 8 == 0, (c, n)
    nb = n // block_rows
    col = pl.BlockSpec((c, 1), lambda i: (0, 0))
    packed, counts = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((c, block_rows), lambda i: (0, i)),
            col, col, col,
        ],
        out_specs=[
            pl.BlockSpec((c, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, n), table_t.dtype),
            jax.ShapeDtypeStruct((nb, 8, 128), jnp.int32),
        ],
        interpret=interpret,
    )(limit, table_t, sel_ops, sel_vals, proj_mask)
    return packed, counts[:, 0, 0]
