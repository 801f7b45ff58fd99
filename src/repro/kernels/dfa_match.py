"""Regular-expression matching kernel (paper §5.3 "Regular expression matching").

TPU adaptation of Farview's parallel regex engines:

  * Farview instantiates multiple spatial regex engines to sustain line rate;
    here the engines are VPU *lanes*: each lane runs one string's DFA.
  * FPGA state machines use LUT transition logic; TPUs have no cheap gather,
    so the per-character transition is computed as two MXU matmuls over
    one-hot encodings:   U = T^t @ OneHot(state)  -> (256, R)
                         next = sum_c U * OneHot(char) -> (R,)
    i.e. the MXU evaluates *all* transitions and the char one-hot selects.
  * As in the paper, throughput depends only on string length, never on
    pattern complexity (the DFA is precompiled host-side; see
    repro.core.regex for the regex -> NFA -> DFA compiler).

Strings are stored transposed (L, N) so the time step indexes the sublane
axis (dynamic sublane slices are TPU-friendly; dynamic lane slices are not).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 128  # strings per block (lanes)
ALPHA = 256


def _kernel(n_states, seq_len, chars_ref, len_ref, table_ref, accept_ref,
            out_ref):
    lens = len_ref[...]                                       # (1, R) int32
    table_t = table_ref[...]                                  # (256, S) f32 (T^t)
    accept = accept_ref[...]                                  # (1, S) f32
    r = lens.shape[1]
    s = n_states

    iota_s = jax.lax.broadcasted_iota(jnp.int32, (s, r), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (ALPHA, r), 0)

    def step(t, state):                                       # state (1, R)
        ch = chars_ref[pl.ds(t, 1), :]                        # (1, R)
        st_oh = (state == iota_s).astype(jnp.float32)             # (S, R)
        ch_oh = (ch == iota_c).astype(jnp.float32)                # (256, R)
        u = jax.lax.dot(table_t, st_oh,
                        precision=jax.lax.Precision.HIGHEST)      # (256, R)
        nxt = jnp.sum(u * ch_oh, axis=0, keepdims=True)           # (1, R)
        nxt = jnp.round(nxt).astype(jnp.int32)
        return jnp.where(t < lens, nxt, state)

    state = jax.lax.fori_loop(0, seq_len, step,
                              jnp.zeros((1, r), jnp.int32))
    st_oh = (state == iota_s).astype(jnp.float32)
    acc = jax.lax.dot(accept, st_oh,
                      precision=jax.lax.Precision.HIGHEST)         # (1, R)
    out_ref[...] = (acc > 0.5).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def dfa_match(chars_t: jnp.ndarray, lengths: jnp.ndarray,
              table_t: jnp.ndarray, accept: jnp.ndarray, *,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: bool = True):
    """chars_t: (L, N) int32 transposed strings; lengths: (1, N) int32;
    table_t: (256, S) f32 transition table transpose; accept: (1, S) f32.
    N % block_rows == 0. Returns the (N,) int32 match mask.
    """
    l, n = chars_t.shape
    s = table_t.shape[1]
    assert n % block_rows == 0
    kern = functools.partial(_kernel, s, l)
    out = pl.pallas_call(
        kern,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((l, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((ALPHA, s), lambda i: (0, 0)),
            pl.BlockSpec((1, s), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(chars_t, lengths, table_t, accept)
    return out.reshape(n)
