"""What every table kind shares: the table the reference reads, the
generator of a seed's numbers, and the cipher of a table held encrypted.

A table kind (`tables/<kind>.py`) makes the queried table with numpy on
the host: the same seed gives the same words on every platform, so a
rehearsal on the CPU and a run on the chip see the same data, and the
reference reads them. It makes the pool's other tables on the device,
where nothing reads them but the pool.

Where the configuration holds its tables encrypted at rest, each is CTR
ciphertext made with the benchmark's own cipher (Threefry 2x32, 20
rounds, keystream positional over the row-major flattening), the queried
table under the configuration's nonce (`encrypt`, here) and table t under
nonce + t (`crypt_chunk`, inside the table kind's device call). The
program decrypts with its own code, and the reference never decrypts at
all: it reads the plaintext.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

CHUNK_WORDS = 1 << 24       # cipher chunk: 64 MiB of words per device call
_PARITY = np.uint32(0x1BD11BDA)
_ROTS = (13, 15, 26, 6, 17, 29, 16, 24)


@dataclass
class Table:
    words: np.ndarray       # (rows, n_cols) float32, row-major
    columns: list           # the configuration's column entries

    def index(self, name: str) -> int:
        for i, c in enumerate(self.columns):
            if c["name"] == name:
                return i
        raise KeyError(f"no column {name!r}")


def rng_of(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole seed, negative or past 64 bits included."""
    return np.random.default_rng([seed % (1 << 64), stream])


# ------------------------------------------------------------- the cipher
def _threefry2x32(key, c0, c1):
    import jax.numpy as jnp

    def rotl(x, r):
        return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))
    k0, k1 = key[0], key[1]
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0, x1 = c0 + ks[0], c1 + ks[1]
    for block in range(5):
        for r in range(4):
            x0 = x0 + x1
            x1 = rotl(x1, _ROTS[(4 * block + r) % 8])
            x1 = x0 ^ x1
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def crypt_chunk(data, start, key, nonce):
    """data, uint32 of any shape, at stream positions start + its row-major
    index."""
    import jax
    import jax.numpy as jnp
    idx = jnp.full(data.shape, start, jnp.uint32)
    stride = 1
    for axis in reversed(range(data.ndim)):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, data.shape,
                                             axis) * jnp.uint32(stride)
        stride *= data.shape[axis]
    s0, s1 = _threefry2x32(key, idx >> 1, jnp.full_like(idx, nonce))
    return data ^ jnp.where((idx & 1) == 0, s0, s1)


def encrypt(words: np.ndarray, at_rest: dict) -> np.ndarray:
    """CTR ciphertext of `words` (row-major flattening, from position 0)."""
    import jax
    import jax.numpy as jnp
    flat = words.reshape(-1).view(np.uint32)
    chunk = min(CHUNK_WORDS, flat.size)
    fn = jax.jit(crypt_chunk)
    key = jnp.asarray(at_rest["key"], jnp.uint32)
    nonce = jnp.uint32(at_rest["nonce"])
    out = np.empty_like(flat)
    buf = np.zeros(chunk, np.uint32)
    for lo in range(0, flat.size, chunk):
        n = min(chunk, flat.size - lo)
        buf[:n] = flat[lo: lo + n]
        buf[n:] = 0
        out[lo: lo + n] = np.asarray(
            fn(jnp.asarray(buf), jnp.uint32(lo), key, nonce))[:n]
    return out.view(np.float32).reshape(words.shape)
