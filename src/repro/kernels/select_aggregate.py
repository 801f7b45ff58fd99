"""Streaming selection + exact scalar aggregate kernel (TPC-H Q6's shape).

The select kernel's stream (kernels/select_project.py) with the packing
replaced by a reduction: the grid streams column-major (C, R) blocks, the
predicate is the shared helper (`ref.eval_predicate`) over the whole
block, and each selected row adds its terms into per-lane accumulators
that stay in VMEM across the grid (the output block's index never moves).

Words are f32, so sums are kept exactly in int32 limbs. Each term's
per-row value (one column, or the product of two, |x| < 2^24) is split
into five base-2^12 digits (`ref.product_digits`); each lane adds them
into five accumulators and carries after every block, so the low four
stay in [0, 4096) and the top one moves by at most 2 a block: exact for
2^30 blocks. What leaves is (L, R) int32: row 0 counts the selected rows
per lane, row 1 + 5 t + j holds digit j of term t. The wrapper
(`ops.select_aggregate_cols`) sums the lanes and the host combines the
digits as Python ints (`ref.limb_totals`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

# rows a grid step: no (R, R) permutation bounds the block here, and on
# one v5e a (32, 24 Mi) scan took 35.3 ms at 256 rows a step, 13.1 at
# 1,024, 10.9 at 2,048 and 12.5 at 4,096
DEFAULT_BLOCK_ROWS = 2048


def _kernel(limit_ref, table_ref, ops_ref, vals_ref, acc_ref, *, terms):
    i = pl.program_id(0)
    cols = table_ref[...]                                    # (C, R) f32
    r = cols.shape[1]
    row = i * r + jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    mask = ref.eval_predicate(cols, ops_ref[...], vals_ref[...],
                              col_axis=0, keepdims=True)
    mask = mask & (row < limit_ref[0, 0])                    # (1, R)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)

    acc_ref[0:1, :] = acc_ref[0:1, :] + mask.astype(jnp.int32)
    one = jnp.ones((1, r), jnp.int32)
    for t, term in enumerate(terms):
        a = jnp.where(mask, cols[term[0]:term[0] + 1, :].astype(jnp.int32),
                      0)
        b = (cols[term[1]:term[1] + 1, :].astype(jnp.int32)
             if len(term) == 2 else one)
        base = 1 + ref.N_DIGITS * t
        carry = None
        for j, d in enumerate(ref.product_digits(a, b)):
            k = base + j
            x = acc_ref[k:k + 1, :] + d
            if carry is not None:
                x = x + carry
            if j < ref.N_DIGITS - 1:
                carry = x >> ref.LIMB_BITS
                x = x & ref.LIMB_MASK
            acc_ref[k:k + 1, :] = x


def n_limb_rows(n_terms: int) -> int:
    """Rows of the accumulator: the count and five digits a term."""
    return 1 + ref.N_DIGITS * n_terms


@functools.partial(jax.jit,
                   static_argnames=("terms", "block_rows", "interpret"))
def select_aggregate(table_t: jnp.ndarray, sel_ops: jnp.ndarray,
                     sel_vals: jnp.ndarray, limit: jnp.ndarray, *,
                     terms: tuple, block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = True):
    """Per-lane limb accumulators of the selected rows' count and sums.

    table_t: (C, N) f32, C % 8 == 0, N % block_rows == 0 (wrapper pads).
    sel_ops: (C, S) int32 opcodes; sel_vals: (C, S) f32.
    limit: (1, 1) int32 — rows >= limit are never selected.
    terms: static tuple of one- or two-column index tuples.
    Returns (L, block_rows) int32, L = n_limb_rows(len(terms)) padded to
    a multiple of 8.

    The accumulators are resident across the grid, which a vmap's extra
    grid axis would share, so a vmapped call runs one request at a time.
    """
    return jax.custom_batching.sequential_vmap(functools.partial(
        _select_aggregate, terms=terms, block_rows=block_rows,
        interpret=interpret))(table_t, sel_ops, sel_vals, limit)


def _select_aggregate(table_t, sel_ops, sel_vals, limit, *, terms,
                      block_rows, interpret):
    c, n = table_t.shape
    s = sel_ops.shape[1]
    assert n % block_rows == 0 and c % 8 == 0, (c, n)
    rows = -(-n_limb_rows(len(terms)) // 8) * 8
    slots = pl.BlockSpec((c, s), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, terms=terms),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((c, block_rows), lambda i: (0, i)),
            slots, slots,
        ],
        out_specs=pl.BlockSpec((rows, block_rows), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block_rows), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(limit, table_t, sel_ops, sel_vals)
