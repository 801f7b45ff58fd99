"""xLSTM blocks: mLSTM (matrix memory) + sLSTM (scalar memory).

Follows arXiv:2405.04517. The mLSTM is computed *chunkwise* (TPU-native:
the within-chunk part is causal linear attention on the MXU; the cross-chunk
part is a short lax.scan over chunk states), with the paper's exponential
input gate / log-sigmoid forget gate stabilized by a running max m_t.

The sLSTM has true sequential recurrence (hidden-to-hidden weights), so it
scans over time; xLSTM-125m uses it in a minority of blocks (pattern set by
config), so the scan does not dominate step cost.

Decode: both blocks are recurrent; their state tuple is the "cache" (O(1)
per token — no KV pool; DESIGN.md notes far-KV inapplicability for this
family).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.layers import dense_init, init_rmsnorm, rms_norm


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def init_mlstm(key, d_model, n_heads, dtype, *, proj_factor: float = 2.0):
    dh = int(d_model * proj_factor) // n_heads
    d_inner = dh * n_heads
    ks = jax.random.split(key, 8)
    return {
        "w_up": dense_init(ks[0], d_model, 2 * d_inner, dtype),
        "w_q": dense_init(ks[1], d_inner, d_inner, dtype),
        "w_k": dense_init(ks[2], d_inner, d_inner, dtype),
        "w_v": dense_init(ks[3], d_inner, d_inner, dtype),
        "w_i": dense_init(ks[4], d_inner, n_heads, dtype),
        "w_f": dense_init(ks[5], d_inner, n_heads, dtype),
        "w_o": dense_init(ks[6], d_inner, d_model, dtype,
                          scale=1.0 / math.sqrt(d_inner)),
        "norm": init_rmsnorm(d_inner, dtype),
        "skip": dense_init(ks[7], d_inner, d_inner, dtype),
    }


def _mlstm_chunk_scan(q, k, v, log_f, log_i, chunk: int):
    """Stabilized chunkwise mLSTM scan.

    q/k/v: (B, S, H, Dh); log_f/log_i: (B, S, H). Returns (B, S, H, Dh).
    State: C (B, H, Dh, Dh), n (B, H, Dh), m (B, H).
    """
    b, s, h, dh = q.shape
    nc = max(1, s // chunk)
    cs = s // nc
    qc = q.reshape(b, nc, cs, h, dh)
    kc = k.reshape(b, nc, cs, h, dh)
    vc = v.reshape(b, nc, cs, h, dh)
    lf = log_f.reshape(b, nc, cs, h).astype(jnp.float32)
    li = log_i.reshape(b, nc, cs, h).astype(jnp.float32)

    # within-chunk cumulative forget
    lf_cum = jnp.cumsum(lf, axis=2)                      # (B, nc, cs, H)
    lf_tot = lf_cum[:, :, -1]                            # (B, nc, H)

    def step(carry, inp):
        C, n, m = carry                                  # (B,H,Dh,Dh),(B,H,Dh),(B,H)
        # §Perf C4: the inter-chunk state is CARRIED in bf16 (math in f32).
        # scan saves every per-chunk carry for the backward; in f32 those
        # saves alone exceed the 16 GiB HBM budget at train_4k (18.4 GiB
        # temp measured). bf16 halves them; the normalizer n and max m stay
        # f32 (they carry the numerical conditioning).
        C = C.astype(jnp.float32)
        qb, kb, vb, lfc, lit, lft = inp
        # Stabilizer covering the state update's exponent range; the output
        # y is invariant to the exact m (it cancels), so a per-chunk m that
        # upper-bounds the kv weights is sufficient (xLSTM App. stabilized).
        m_kv = jnp.max(lft[:, None] - lfc + lit, axis=1)  # (B, H)
        m_new = jnp.maximum(m + lft, m_kv)

        # inter-chunk: y_i += q_i @ C * exp(lfc_i + m - m_new)
        # §Perf C5: dots consume q and the carried state in the stream
        # dtype (bf16) with f32 accumulation — the f32 `.astype` versions
        # made XLA materialize full-sequence f32 copies of the stacked
        # q/k/v scan inputs (0.38 GiB each, the top temp-memory holders).
        w_inter = jnp.exp(lfc + m[:, None] - m_new[:, None])   # (B, cs, H)
        y_inter = jnp.einsum("bchd,bhde->bche", qb, C.astype(qb.dtype),
                             optimize=True,
                             preferred_element_type=jnp.float32
                             ) * w_inter[..., None]
        n_inter = jnp.einsum("bchd,bhd->bch", qb, n.astype(qb.dtype),
                             optimize=True,
                             preferred_element_type=jnp.float32) * w_inter

        # intra-chunk: D[i,j] = exp(lfc_i - lfc_j + li_j - m_new), causal
        lw = (lfc[:, :, None, :] - lfc[:, None, :, :]
              + lit[:, None, :, :] - m_new[:, None, None, :])  # (B, ci, cj, H)
        causal = jnp.tril(jnp.ones((cs, cs), bool))
        # mask BEFORE exp: non-causal exponents overflow and poison the
        # backward with inf*0 (see mamba2.ssd_chunk_scan) — this was the
        # source of the xlstm train NaN-gradient events
        lw = jnp.where(causal[None, :, :, None], lw, -1e30)
        D = jnp.exp(lw)
        # §Perf C3: MXU-native — dots consume q/k/v in their stored dtype
        # with f32 accumulation; the decay-weighted A casts to the value
        # dtype for the PV dot (flash-attention-style p handling).
        scores = jnp.einsum("bchd,bkhd->bckh", qb, kb, optimize=True,
                            preferred_element_type=jnp.float32)
        A = scores * D                                     # (B, ci, cj, H)
        y_intra = jnp.einsum("bckh,bkhd->bchd", A.astype(vb.dtype), vb,
                             optimize=True,
                             preferred_element_type=jnp.float32)
        n_intra = jnp.sum(A, axis=2)                       # (B, ci, H)

        y = y_inter + y_intra
        n_i = n_inter + n_intra
        denom = jnp.maximum(jnp.abs(n_i), jnp.exp(-m_new)[:, None])
        y = y / denom[..., None]

        # state update: C' = exp(lft + m - m_new) C + sum_j exp(lft-lfc_j+li_j-m_new) k_j v_j^T
        w_c = jnp.exp(lft + m - m_new)                     # (B, H)
        w_kv = jnp.exp(lft[:, None] - lfc + lit - m_new[:, None])  # (B, cs, H)
        kbw = (kb.astype(jnp.float32) * w_kv[..., None]).astype(vb.dtype)
        kv = jnp.einsum("bchd,bche->bhde", kbw, vb, optimize=True,
                        preferred_element_type=jnp.float32)
        C_new = C * w_c[..., None, None] + kv
        n_add = jnp.einsum("bchd,bch->bhd", kb,
                           w_kv.astype(kb.dtype), optimize=True,
                           preferred_element_type=jnp.float32)
        n_new = n * w_c[..., None] + n_add
        return (C_new.astype(jnp.bfloat16), n_new, m_new), y

    C0 = jnp.zeros((b, h, dh, dh), jnp.bfloat16)
    n0 = jnp.zeros((b, h, dh), jnp.float32)
    m0 = jnp.full((b, h), -1e30, jnp.float32)
    xs = (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(kc, 1, 0),
          jnp.moveaxis(vc, 1, 0), jnp.moveaxis(lf_cum, 1, 0),
          jnp.moveaxis(li, 1, 0), jnp.moveaxis(lf_tot, 1, 0))
    (Cf, nf, mf), ys = jax.lax.scan(step, (C0, n0, m0), xs)
    final = (Cf.astype(jnp.float32), nf, mf)
    return jnp.moveaxis(ys, 0, 1).reshape(b, s, h, dh), final


def mlstm_block(x, p, *, n_heads: int, chunk: int = 256,
                return_state: bool = False):
    """x: (B, S, d_model) -> (B, S, d_model). Pre-norm residual outside."""
    b, s, d = x.shape
    up = x @ p["w_up"]
    xi, gate = jnp.split(up, 2, axis=-1)
    d_inner = xi.shape[-1]
    dh = d_inner // n_heads
    q = (xi @ p["w_q"]).reshape(b, s, n_heads, dh)
    k = ((xi @ p["w_k"]) / math.sqrt(dh)).reshape(b, s, n_heads, dh)
    v = (xi @ p["w_v"]).reshape(b, s, n_heads, dh)
    log_i = (xi @ p["w_i"]).astype(jnp.float32)            # (B, S, H)
    log_f = jax.nn.log_sigmoid((xi @ p["w_f"]).astype(jnp.float32))
    h, final_state = _mlstm_chunk_scan(q, k, v, log_f, log_i, chunk)
    h = h.reshape(b, s, d_inner).astype(x.dtype)
    h = rms_norm(h, p["norm"]) + xi @ p["skip"]
    h = h * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
    out = h @ p["w_o"]
    return (out, final_state) if return_state else out


def mlstm_decode_step(x, p, state, *, n_heads: int):
    """Single-token recurrent step. state = (C, n, m)."""
    b, d = x.shape
    up = x @ p["w_up"]
    xi, gate = jnp.split(up, 2, axis=-1)
    d_inner = xi.shape[-1]
    dh = d_inner // n_heads
    q = (xi @ p["w_q"]).reshape(b, n_heads, dh).astype(jnp.float32)
    k = ((xi @ p["w_k"]) / math.sqrt(dh)).reshape(b, n_heads, dh).astype(jnp.float32)
    v = (xi @ p["w_v"]).reshape(b, n_heads, dh).astype(jnp.float32)
    log_i = (xi @ p["w_i"]).astype(jnp.float32)            # (B, H)
    log_f = jax.nn.log_sigmoid((xi @ p["w_f"]).astype(jnp.float32))
    C, n, m = state
    m_new = jnp.maximum(log_f + m, log_i)
    wf = jnp.exp(log_f + m - m_new)
    wi = jnp.exp(log_i - m_new)
    C = C * wf[..., None, None] + jnp.einsum("bhd,bhe->bhde", k * wi[..., None], v)
    n = n * wf[..., None] + k * wi[..., None]
    num = jnp.einsum("bhd,bhde->bhe", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(b, d_inner).astype(x.dtype)
    h = rms_norm(h, p["norm"]) + xi @ p["skip"]
    h = h * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
    return h @ p["w_o"], (C, n, m_new)


def mlstm_init_state(batch, d_model, n_heads, *, proj_factor: float = 2.0):
    d_inner = int(d_model * proj_factor)
    dh = d_inner // n_heads
    return (jnp.zeros((batch, n_heads, dh, dh), jnp.float32),
            jnp.zeros((batch, n_heads, dh), jnp.float32),
            jnp.full((batch, n_heads), -1e30, jnp.float32))


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def init_slstm(key, d_model, n_heads, dtype):
    dh = d_model // n_heads
    ks = jax.random.split(key, 10)
    p = {"norm": init_rmsnorm(d_model, dtype)}
    for i, g in enumerate(("i", "f", "z", "o")):
        p[f"w_{g}"] = dense_init(ks[i], d_model, d_model, dtype)
        # block-diagonal recurrent weights: per-head (dh, dh)
        p[f"r_{g}"] = (jax.random.normal(ks[4 + i], (n_heads, dh, dh),
                                         jnp.float32) / math.sqrt(dh)).astype(dtype)
    p["w_out"] = dense_init(ks[8], d_model, d_model, dtype)
    return p


def _slstm_scan(pre_t, r_i, r_f, r_z, r_o, n_heads: int):
    """The sequential gate recurrence. pre_t: 4-tuple of (S, B, H, dh)."""
    recs = {"i": r_i, "f": r_f, "z": r_z, "o": r_o}

    def step(carry, xs_t):
        c, n, h, m = carry                   # (B,H,dh) x3, (B,H)
        pi, pf, pz, po = xs_t
        gates = {}
        for g, pg in (("i", pi), ("f", pf), ("z", pz), ("o", po)):
            rec = jnp.einsum("bhd,hde->bhe", h, recs[g].astype(jnp.float32))
            gates[g] = pg.astype(jnp.float32) + rec
        log_i = jnp.mean(gates["i"], axis=-1)               # per-head gate
        log_f = jax.nn.log_sigmoid(jnp.mean(gates["f"], axis=-1))
        m_new = jnp.maximum(log_f + m, log_i)
        wi = jnp.exp(log_i - m_new)[..., None]
        wf = jnp.exp(log_f + m - m_new)[..., None]
        z = jnp.tanh(gates["z"])
        o = jax.nn.sigmoid(gates["o"])
        c_new = wf * c + wi * z
        n_new = wf * n + wi
        h_new = o * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, h_new, m_new), h_new

    b = pre_t[0].shape[1]
    dh = pre_t[0].shape[3]
    z0 = jnp.zeros((b, n_heads, dh), jnp.float32)
    carry0 = (z0, z0, z0, jnp.full((b, n_heads), -1e30, jnp.float32))
    return jax.lax.scan(step, carry0, pre_t)


def slstm_block(x, p, *, n_heads: int, return_state: bool = False,
                mesh=None, dp_axes=("data",)):
    """Sequential scan over time. x: (B, S, d) -> (B, S, d).

    §Perf C1/C2: a strict h->h recurrence cannot be sequence-sharded —
    every step t needs step t-1. Two bad lowerings were measured on
    xlstm-125m train_4k before this form:
      * closing over the seq-sharded (B,S,d) buffer and indexing per step
        -> GSPMD full-local-buffer masked select every timestep
        (~600 GB/device/step, 75%% of the whole train step);
      * replicating via with_sharding_constraint under GSPMD -> correct
        forward, but the backward emitted a per-TIMESTEP all-reduce of the
        recurrent-weight gradients (54 GiB/step).
    Under a mesh the scan therefore runs inside shard_map: the gate
    pre-activations are all_gathered over "model" ONCE, the recurrence is
    computed redundantly on every model-axis device (its FLOPs are tiny),
    the output is sliced back to the local sequence chunk, and the
    recurrent-weight gradients psum ONCE at the region boundary.
    """
    b, s, d = x.shape
    dh = d // n_heads
    pre = {g: x @ p[f"w_{g}"] for g in ("i", "f", "z", "o")}

    if mesh is None:
        pre_t = tuple(
            jnp.moveaxis(pre[g].reshape(b, s, n_heads, dh), 1, 0)
            for g in ("i", "f", "z", "o"))
        final, hs = _slstm_scan(pre_t, p["r_i"], p["r_f"], p["r_z"],
                                p["r_o"], n_heads)
        h = jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(x.dtype)
        out = rms_norm(h, p["norm"]) @ p["w_out"]
        return (out, final) if return_state else out

    from jax.sharding import PartitionSpec as P
    dpa = tuple(dp_axes) if dp_axes else ()

    def sm(pi, pf, pz, po, ri, rf, rz, ro):
        # each (B_loc, S_loc, d): gather the full sequence once
        full = [jax.lax.all_gather(v, "model", axis=1, tiled=True)
                for v in (pi, pf, pz, po)]
        bl, sf = full[0].shape[0], full[0].shape[1]
        pre_t = tuple(jnp.moveaxis(v.reshape(bl, sf, n_heads, dh), 1, 0)
                      for v in full)
        final, hs = _slstm_scan(pre_t, ri, rf, rz, ro, n_heads)
        hs = jnp.moveaxis(hs, 0, 1)          # (B_loc, S, H, dh)
        s_loc = pi.shape[1]
        idx = jax.lax.axis_index("model") * s_loc
        h_loc = jax.lax.dynamic_slice(
            hs, (0, idx, 0, 0), (bl, s_loc, n_heads, dh))
        return h_loc, final

    args = [pre[g] for g in ("i", "f", "z", "o")]
    args += [p[f"r_{g}"] for g in ("i", "f", "z", "o")]
    h_loc, final = shard_map(
        sm, mesh=mesh,
        in_specs=(P(dpa or None, "model", None),) * 4
        + (P(None, None, None),) * 4,
        out_specs=(P(dpa or None, "model", None, None),
                   jax.tree.map(lambda _: P(dpa or None),
                                (0, 0, 0, 0))),
        check_vma=False)(*args)
    h = h_loc.reshape(b, s, d).astype(x.dtype)
    out = rms_norm(h, p["norm"]) @ p["w_out"]
    return (out, final) if return_state else out


def slstm_decode_step(x, p, state, *, n_heads: int):
    b, d = x.shape
    dh = d // n_heads
    c, n, h, m = state
    gates = {}
    for g in ("i", "f", "z", "o"):
        rec = jnp.einsum("bhd,hde->bhe", h, p[f"r_{g}"].astype(jnp.float32))
        gates[g] = (x @ p[f"w_{g}"]).reshape(b, n_heads, dh).astype(jnp.float32) + rec
    log_i = jnp.mean(gates["i"], axis=-1)
    log_f = jax.nn.log_sigmoid(jnp.mean(gates["f"], axis=-1))
    m_new = jnp.maximum(log_f + m, log_i)
    wi = jnp.exp(log_i - m_new)[..., None]
    wf = jnp.exp(log_f + m - m_new)[..., None]
    z = jnp.tanh(gates["z"])
    o = jax.nn.sigmoid(gates["o"])
    c_new = wf * c + wi * z
    n_new = wf * n + wi
    h_new = o * c_new / jnp.maximum(n_new, 1e-6)
    out = rms_norm(h_new.reshape(b, d).astype(x.dtype), p["norm"]) @ p["w_out"]
    return out, (c_new, n_new, h_new, m_new)


def slstm_init_state(batch, d_model, n_heads):
    dh = d_model // n_heads
    z = jnp.zeros((batch, n_heads, dh), jnp.float32)
    return (z, z, z, jnp.full((batch, n_heads), -1e30, jnp.float32))
