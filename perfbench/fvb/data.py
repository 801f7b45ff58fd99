"""The tables of a configuration, made from the seed.

The queried table is made with numpy on the host: the same seed gives the
same words on every platform, so a rehearsal on the CPU and a run on the
chip see the same data, and the reference reads them. The columns follow
the configuration's `columns` list, each drawn by its `gen`: `keys`,
`int` in [low, high), or `uniform` in [0, 1). The group key takes the
`keys.distinct` values of one key set, uniform over the rows. The key set
is drawn at random from [low, high) by the configuration's
`keys.set_seed`, not by the run's seed, so that every run groups the same
keys and only the rows' order and values change with the seed.

The pool's other tables (`tables` in all) have the same columns and are
drawn on the device from the seed, one jitted call each; nothing reads
them but the pool.

Where the configuration holds its tables encrypted at rest, each is CTR
ciphertext made with the benchmark's own cipher (Threefry 2x32, 20
rounds, keystream positional over the row-major flattening), the queried
table under the configuration's nonce and table t under nonce + t. The
program decrypts with its own code, and the reference never decrypts at
all: it reads the plaintext.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import numpy as np

CHUNK_WORDS = 1 << 24       # cipher chunk: 64 MiB of words per device call
_PARITY = np.uint32(0x1BD11BDA)
_ROTS = (13, 15, 26, 6, 17, 29, 16, 24)


@dataclass
class Table:
    words: np.ndarray       # (rows, n_cols) float32, row-major
    columns: list           # the configuration's column entries
    keys: np.ndarray        # the distinct group keys, int32

    def index(self, name: str) -> int:
        for i, c in enumerate(self.columns):
            if c["name"] == name:
                return i
        raise KeyError(f"no column {name!r}")


def rng_of(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole seed, negative or past 64 bits included."""
    return np.random.default_rng([seed % (1 << 64), stream])


def draw_keys(spec: dict) -> np.ndarray:
    """`distinct` keys drawn at random from [low, high), without
    repetition, by `set_seed`."""
    rng = rng_of(int(spec["set_seed"]), stream=3)
    keys = rng.choice(int(spec["high"]) - int(spec["low"]),
                      size=int(spec["distinct"]), replace=False)
    return (keys + int(spec["low"])).astype(np.int32)


def make_table(config: dict, seed: int, rows: int) -> Table:
    cols = config["columns"]
    rng = rng_of(seed)
    keys = draw_keys(config["keys"])
    words = np.empty((rows, len(cols)), np.float32)
    i = 0
    while i < len(cols):
        # a run of neighbouring columns of one kind is drawn as one block
        kind = _kind(cols[i])
        j = i + 1
        while j < len(cols) and _kind(cols[j]) == kind:
            j += 1
        shape = (rows, j - i)
        if kind[0] == "keys":
            words[:, i:j] = keys[rng.integers(0, len(keys), shape,
                                              dtype=np.int32)]
        elif kind[0] == "int":
            words[:, i:j] = rng.integers(kind[1], kind[2], shape,
                                         dtype=np.int32)
        elif kind[0] == "uniform":
            words[:, i:j] = rng.random(shape, dtype=np.float32)
        else:
            raise ValueError(f"column {cols[i]['name']}: unknown gen "
                             f"{kind[0]!r}")
        i = j
    return Table(words=words, columns=cols, keys=keys)


def _kind(col: dict) -> tuple:
    return (col["gen"], col.get("low"), col.get("high"))


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


def _crypt_chunk(data, start, key, nonce):
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
    fn = jax.jit(_crypt_chunk)
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


# ------------------------------------------------ the pool's other tables
_GENS = {"int": 0, "keys": 1, "uniform": 2}


def _lookup(table, idx):
    """table[idx] for a short table, by a tree of selects on the bits of
    idx: a gather of every word is slow on the TPU."""
    import jax.numpy as jnp
    vals = [table[i] for i in range(table.shape[0])]
    vals += vals[-1:] * ((1 << (len(vals) - 1).bit_length()) - len(vals))
    bit = 0
    while len(vals) > 1:
        odd = ((idx >> jnp.uint32(bit)) & jnp.uint32(1)) == 1
        vals = [jnp.where(odd, vals[i + 1], vals[i])
                for i in range(0, len(vals), 2)]
        bit += 1
    return vals[0]


def _other_words(key, gen, low, span, keys, ckey, nonce, *, rows: int,
                 encrypted: bool):
    """One table's words, drawn from `key`, flat in row-major order. They
    are drawn as (m, lanes), `lanes` a multiple of both 128 and the row's
    words, so that each lane holds one column and its parameters broadcast
    along the rows."""
    import jax
    import jax.numpy as jnp
    n_cols = gen.shape[0]
    lanes = n_cols * 128 // math.gcd(n_cols, 128)
    if rows * n_cols % lanes:
        raise ValueError(f"{rows} rows of {n_cols} words do not fill "
                         f"rows of {lanes} lanes")
    shape = (rows * n_cols // lanes, lanes)
    g, lo, sp = (jnp.tile(x, lanes // n_cols)[None, :]
                 for x in (gen, low, span))
    bits = jax.random.bits(key, shape, jnp.uint32)
    uniform = jax.lax.bitcast_convert_type(
        (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
    integer = (lo + (bits % sp).astype(jnp.int32)).astype(jnp.float32)
    key_word = _lookup(keys, bits % jnp.uint32(keys.shape[0])
                       ).astype(jnp.float32)
    words = jnp.where(g == _GENS["uniform"], uniform,
                      jnp.where(g == _GENS["keys"], key_word, integer))
    if encrypted:
        u = jax.lax.bitcast_convert_type(words, jnp.uint32)
        words = jax.lax.bitcast_convert_type(
            _crypt_chunk(u, jnp.uint32(0), ckey, nonce), jnp.float32)
    return words.reshape(-1)


@functools.lru_cache(maxsize=None)
def _other_words_fn(rows: int, encrypted: bool):
    import jax
    return jax.jit(functools.partial(_other_words, rows=rows,
                                     encrypted=encrypted))


def other_table(config: dict, seed: int, t: int, rows: int):
    """Table `t` (1 to `tables` - 1) of the pool: the configuration's
    columns drawn on the device from the seed, as a flat float32 device
    array in row-major order; ciphertext under nonce + t where the tables
    are held encrypted at rest."""
    import jax
    import jax.numpy as jnp
    cols = config["columns"]
    gen = np.asarray([_GENS[c["gen"]] for c in cols], np.int32)
    low = np.asarray([c.get("low", 0) for c in cols], np.int32)
    span = np.asarray([c.get("high", 1) - c.get("low", 0) for c in cols],
                      np.uint32)
    words = rng_of(seed, stream=4).integers(0, 1 << 32, 2, dtype=np.uint64)
    key = jax.random.fold_in(jax.random.wrap_key_data(
        jnp.asarray(words.astype(np.uint32)), impl="threefry2x32"), t)
    at_rest = config.get("encrypted_at_rest")
    fn = _other_words_fn(rows, bool(at_rest))
    ckey = jnp.asarray(at_rest["key"] if at_rest else (0, 0), jnp.uint32)
    nonce = jnp.uint32((at_rest["nonce"] if at_rest else 0) + t)
    return fn(key, gen, low, span, draw_keys(config["keys"]), ckey, nonce)
