"""Counter-mode stream cipher kernel (paper §5.5 encryption/decryption).

TPU adaptation of Farview's AES-128-CTR engine:

  * AES's S-box is an 8-bit table lookup — free in FPGA LUTs, hostile to the
    TPU VPU (no cheap gather). We keep the *system role* (CTR-mode stream
    cipher fused into the read/write data path, encrypt == decrypt) and swap
    the round function for an ARX design (Threefry-2x32, 20 rounds), which is
    pure add/rotate/xor and vectorizes perfectly over lanes.
  * Like the paper's "fully parallelized and pipelined" AES, the keystream
    for every word of a block is computed independently, so the cipher runs
    at whatever rate the HBM->VMEM stream sustains: zero throughput penalty,
    which is exactly the claim of Fig. 11 that bench_crypto.py re-validates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 128)
DEFAULT_COL_BLOCK = 512
_ROTS = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _threefry2x32(k0, k1, c0, c1):
    ks = [k0, k1, k0 ^ k1 ^ _PARITY]
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


def _kernel(block_shape, row_words, data_ref, key_ref, out_ref):
    rows, cols = block_shape
    data = data_ref[...]
    k0 = key_ref[0, 0]
    k1 = key_ref[0, 1]
    nonce = key_ref[0, 2]
    step = pl.program_id(0).astype(jnp.uint32)

    ir = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    ic = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    if row_words is None:       # contiguous row-major stream
        pos = step * np.uint32(rows * cols) + ir * np.uint32(cols) + ic
    else:                       # column-major (row_words, N): word [c, r]
        pos = (step * np.uint32(cols) + ic) * np.uint32(row_words) + ir
    ctr = pos >> np.uint32(1)
    lane = pos & np.uint32(1)
    s0, s1 = _threefry2x32(k0, k1, ctr, jnp.full_like(ctr, nonce))
    stream = jnp.where(lane == 0, s0, s1)
    out_ref[...] = data ^ stream


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ctr_crypt(data: jnp.ndarray, key: jnp.ndarray, *,
              block: tuple[int, int] = DEFAULT_BLOCK,
              interpret: bool = True):
    """data: (N, C) uint32 with N % block[0] == 0, C == block[1];
    key: (1, 4) uint32 = [k0, k1, nonce, 0]. Involutive (CTR mode)."""
    n, c = data.shape
    rows, cols = block
    assert n % rows == 0 and c == cols, (data.shape, block)
    kern = functools.partial(_kernel, block, None)
    return pl.pallas_call(
        kern,
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.uint32),
        interpret=interpret,
    )(data, key)


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def ctr_crypt_cols(data_t: jnp.ndarray, key: jnp.ndarray, *,
                   block_cols: int = DEFAULT_COL_BLOCK,
                   interpret: bool = True):
    """The same cipher over a column-major table: data_t (A, N) uint32
    holds word c of row r at [c, r], which is stream position r*A + c of
    the row-major flattening. N % block_cols == 0. Involutive."""
    a, n = data_t.shape
    assert n % block_cols == 0, (data_t.shape, block_cols)
    kern = functools.partial(_kernel, (a, block_cols), a)
    return pl.pallas_call(
        kern,
        grid=(n // block_cols,),
        in_specs=[
            pl.BlockSpec((a, block_cols), lambda i: (0, i)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((a, block_cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((a, n), jnp.uint32),
        interpret=interpret,
    )(data_t, key)
