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
  * the grid runs in order ("arbitrary") and carries a write offset across
    it, so the output comes out globally compacted in the same pass: each
    block's survivors are rotated to the offset in a VMEM window of two
    blocks, and every full block of survivors is DMA'd to the next
    block-aligned place of the output. This is the paper's sender unit:
    its length-prefixed packets of survivors leave back to back, and here
    the stream of them is the answer itself, survivors [0, count) and zeros
    past them, with no per-block lengths left to stitch.

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
_RING = 8      # output blocks in flight: a slot's DMA is awaited on reuse


def _kernel(limit_ref, table_ref, ops_ref, vals_ref, proj_ref, zeros_ref,
            out_ref, count_ref, win_ref, ring_ref, state_ref, sems):
    del zeros_ref                          # aliased to out_ref: the zero tail
    cols = table_ref[...]                                    # (C, R) f32
    proj = proj_ref[...]                                     # (C, 1) f32
    f32 = jnp.float32
    r = cols.shape[1]
    i = pl.program_id(0)

    # --- predicate (VPU): every column's slots (C, S), OP_SKIP holds --------
    # rows at or past `limit` (tail padding, masked n_valid rows) never match
    row = i * r + jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    mask = ref.eval_predicate(cols, ops_ref[...], vals_ref[...], col_axis=0,
                              keepdims=True) & (row < limit_ref[0, 0])
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
    packed = permute_bits(bits, perm)         # (C, R) i32, zeros past n_kept
    n_kept = jnp.sum(mask_f).astype(jnp.int32)

    # --- global compaction: a write offset carried across the grid ----------
    # state: [fill = survivors waiting in the window, blocks written]
    @pl.when(i == 0)
    def _():
        win_ref[...] = jnp.zeros(win_ref.shape, jnp.int32)
        state_ref[0] = 0
        state_ref[1] = 0

    def copy(slot, blk):
        dst = out_ref.at[:, pl.ds(pl.multiple_of(blk * r, r), r)]
        return pltpu.make_async_copy(ring_ref.at[slot], dst, sems.at[slot])

    def emit():
        """Send the window's first block to the next output block and
        shift the window down by a block."""
        blk = state_ref[1]
        slot = blk % _RING

        @pl.when(blk >= _RING)
        def _():
            copy(slot, blk - _RING).wait()
        ring_ref[slot] = win_ref[:, :r]
        copy(slot, blk).start()
        win_ref[:, :r] = win_ref[:, r:]
        win_ref[:, r:] = jnp.zeros((win_ref.shape[0], r), jnp.int32)
        state_ref[1] = blk + 1

    # fill < R and n_kept <= R: the rotation wraps only zeros round
    fill = state_ref[0]
    wide = jnp.concatenate([packed, jnp.zeros_like(packed)], axis=1)
    win_ref[...] = win_ref[...] | pltpu.roll(wide, fill, 1)
    fill = fill + n_kept
    state_ref[0] = fill

    @pl.when(fill >= r)
    def _():
        emit()
        state_ref[0] = fill - r

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        count_ref[0, 0] = state_ref[1] * r + state_ref[0]

        @pl.when(state_ref[0] > 0)
        def _():
            emit()                                  # the partial last block
        for slot in range(_RING):     # drain the ring (a wait needs only
                                      # the copy's size and semaphore)
            @pl.when(slot < state_ref[1])
            def _():
                copy(slot, 0).wait()


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
    """Globally packed survivors + their count, column-major.

    table_t: (C, N) f32, C % 8 == 0, N % block_rows == 0 (wrapper pads).
    sel_ops: (C, S) int32 opcodes, S predicate slots a column;
    sel_vals: (C, S) f32; proj_mask: (C, 1) f32.
    limit: (1, 1) int32 — rows >= limit never survive.
    Returns: packed (C, N) f32 — survivors in row order in lanes
    [0, count), zeros past them — and count, a scalar i32.

    The carried offset cannot take a vmap's extra grid axis (its output is
    written by DMA, from HBM), so a vmapped call runs one request at a time.
    """
    return jax.custom_batching.sequential_vmap(functools.partial(
        _select_project, block_rows=block_rows, interpret=interpret))(
            table_t, sel_ops, sel_vals, proj_mask, limit)


def _select_project(table_t, sel_ops, sel_vals, proj_mask, limit, *,
                    block_rows, interpret):
    c, n = table_t.shape
    assert n % block_rows == 0 and c % 8 == 0, (c, n)
    col = pl.BlockSpec((c, 1), lambda i: (0, 0))
    slots = pl.BlockSpec((c, sel_ops.shape[1]), lambda i: (0, 0))
    packed, count = pl.pallas_call(
        _kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((c, block_rows), lambda i: (0, i)),
            slots, slots, col,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, n), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((c, 2 * block_rows), jnp.int32),      # the window
            pltpu.VMEM((_RING, c, block_rows), jnp.int32),   # blocks in flight
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((_RING,)),
        ],
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(limit, table_t, sel_ops, sel_vals, proj_mask,
      jnp.zeros((c, n), jnp.int32))
    return jax.lax.bitcast_convert_type(packed, jnp.float32), count[0, 0]
