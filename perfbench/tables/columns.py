"""The table kind `columns`: every column drawn on its own.

The columns follow the configuration's `columns` list, each drawn by its
`gen`: `keys`, `int` in [low, high), or `uniform` in [0, 1). The group key
takes the `keys.distinct` values of one key set, uniform over the rows.
The key set is drawn at random from [low, high) by the configuration's
`keys.set_seed`, not by the run's seed, so that every run groups the same
keys and only the rows' order and values change with the seed.

The pool's other tables (`tables` in all) have the same columns and are
drawn on the device from the seed, one jitted call each, encrypted in the
same call where the configuration holds its tables encrypted at rest.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from fvb.data import Table, crypt_chunk, rng_of


def draw_keys(spec: dict) -> np.ndarray:
    """`distinct` keys drawn at random from [low, high), without
    repetition, by `set_seed`."""
    rng = rng_of(int(spec["set_seed"]), stream=3)
    keys = rng.choice(int(spec["high"]) - int(spec["low"]),
                      size=int(spec["distinct"]), replace=False)
    return (keys + int(spec["low"])).astype(np.int32)


def make_table(config: dict, seed: int, rows: int) -> Table:
    """The queried table, on the host."""
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
    return Table(words=words, columns=cols)


def _kind(col: dict) -> tuple:
    return (col["gen"], col.get("low"), col.get("high"))


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
            crypt_chunk(u, jnp.uint32(0), ckey, nonce), jnp.float32)
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
