"""Block assembly per architecture family.

Every architecture is expressed as a stack of identical *groups* scanned
with `jax.lax.scan` (stacked params, leading group axis) so the HLO stays
small and compile time flat in depth. A group bundles the repeating pattern:

  dense / moe / audio : 1 block               x n_layers groups
  gemma2              : (local, global) pair  x n_layers/2 groups
  vlm (llama-vision)  : 4 self + 1 cross      x n_layers/5 groups
  ssm (xlstm)         : (k-1) mLSTM + 1 sLSTM x n_layers/k groups
  hybrid (zamba2)     : k Mamba2 + shared attn x n_layers/k groups
                        (the shared attention block's params are NOT stacked
                        — one set, applied between groups, per zamba2)

Each family implements: init_group / group_fwd (train & prefill; emits KV) /
group_dec (single-token decode vs caches) / group_cache (cache zeros).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import xlstm as XL


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def n_groups(cfg: ModelConfig) -> int:
    per = group_layout(cfg)["layers_per_group"]
    assert cfg.n_layers % per == 0, (cfg.arch, cfg.n_layers, per)
    return cfg.n_layers // per


def group_layout(cfg: ModelConfig) -> dict:
    """Describes the repeating sub-layer pattern of one group."""
    if cfg.family in ("dense", "audio"):
        return {"layers_per_group": 1, "subs": ["attn"]}
    if cfg.family == "moe":
        return {"layers_per_group": 1, "subs": ["attn"]}
    if cfg.attn_pattern == "gemma2_alt":
        return {"layers_per_group": 2, "subs": ["attn_local", "attn_global"]}
    if cfg.family == "vlm":
        k = cfg.cross_every
        return {"layers_per_group": k, "subs": ["attn"] * (k - 1) + ["cross"]}
    if cfg.family == "ssm":        # xlstm
        k = cfg.slstm_every or cfg.n_layers
        k = min(k, cfg.n_layers)
        return {"layers_per_group": k,
                "subs": ["mlstm"] * (k - 1) + ["slstm"]}
    if cfg.family == "hybrid":     # zamba2
        k = cfg.shared_attn_every
        return {"layers_per_group": k, "subs": ["mamba"] * k + ["shared"]}
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_tf_layer(key, cfg: ModelConfig, *, is_moe: bool, post_norm=False):
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {
        "ln_attn": L.init_rmsnorm(cfg.d_model, dt),
        "attn": L.init_attention(k1, cfg.d_model, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.resolved_head_dim, dt),
        "ln_mlp": L.init_rmsnorm(cfg.d_model, dt),
    }
    if post_norm:
        p["ln_attn_post"] = L.init_rmsnorm(cfg.d_model, dt)
        p["ln_mlp_post"] = L.init_rmsnorm(cfg.d_model, dt)
    if is_moe:
        p["moe"] = MOE.init_moe(k2, cfg.d_model, cfg.d_expert,
                                cfg.n_experts, dt)
    else:
        p["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, dt)
    return p


def init_group(key, cfg: ModelConfig) -> dict:
    lay = group_layout(cfg)
    dt = _dtype(cfg)
    p: dict = {}
    keys = jax.random.split(key, len(lay["subs"]) + 1)
    gemma = cfg.attn_pattern == "gemma2_alt"
    for i, sub in enumerate(lay["subs"]):
        k = keys[i]
        if sub in ("attn", "attn_local", "attn_global", "cross"):
            p[f"{sub}_{i}"] = _init_tf_layer(
                k, cfg, is_moe=cfg.family == "moe", post_norm=gemma)
            if sub == "cross":
                # cross-attention has its own kv projections over image tokens
                p[f"{sub}_{i}"]["ln_xattn"] = L.init_rmsnorm(cfg.d_model, dt)
        elif sub == "mlstm":
            p[f"{sub}_{i}"] = {"ln": L.init_rmsnorm(cfg.d_model, dt),
                               "core": XL.init_mlstm(k, cfg.d_model,
                                                     cfg.n_heads, dt)}
        elif sub == "slstm":
            p[f"{sub}_{i}"] = {"ln": L.init_rmsnorm(cfg.d_model, dt),
                               "core": XL.init_slstm(k, cfg.d_model,
                                                     cfg.n_heads, dt)}
        elif sub == "mamba":
            p[f"{sub}_{i}"] = {"ln": L.init_rmsnorm(cfg.d_model, dt),
                               "core": M2.init_mamba2(k, cfg.d_model,
                                                      cfg.n_heads,
                                                      cfg.ssm_state, dt,
                                                      expand=cfg.ssm_expand)}
        elif sub == "shared":
            pass  # shared params live outside the stacked groups
    return p


def init_shared(key, cfg: ModelConfig) -> dict:
    """Non-stacked shared params (zamba2 shared attention block)."""
    if cfg.family == "hybrid" and cfg.shared_attn_every:
        return {"shared_attn": _init_tf_layer(key, cfg, is_moe=False)}
    return {}


def init_stacked(key, cfg: ModelConfig) -> tuple[dict, dict]:
    g = n_groups(cfg)
    keys = jax.random.split(key, g)
    stacked = jax.vmap(lambda k: init_group(k, cfg))(keys)
    shared = init_shared(jax.random.fold_in(key, 987), cfg)
    return stacked, shared


# ---------------------------------------------------------------------------
# forward (train / prefill) — group body
# ---------------------------------------------------------------------------
def _tf_layer_fwd(x, p, cfg: ModelConfig, *, window=0, softcap=None,
                  kv_override=None, causal=True, positions=None,
                  collect_kv=False, mesh=None, dp_axes=("data",)):
    gemma = cfg.attn_pattern == "gemma2_alt"
    h = L.rms_norm(x, p["ln_attn"], cfg.norm_eps)
    attn_out, kv = L.attention(
        h, p["attn"], n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, causal=causal, window=window,
        softcap=softcap, rope_theta=cfg.rope_theta, positions=positions,
        kv_chunk=cfg.kv_chunk, kv_override=kv_override)
    if gemma:
        attn_out = L.rms_norm(attn_out, p["ln_attn_post"], cfg.norm_eps)
    x = x + attn_out
    h = L.rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    aux = 0.0
    if "moe" in p:
        if mesh is not None:
            # §Perf A1: explicit all_to_all expert parallelism
            mlp_out, aux = MOE.moe_ffn_a2a(
                h, p["moe"], top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, act=cfg.act,
                mesh=mesh, dp_axes=dp_axes)
        else:
            mlp_out, aux = MOE.moe_ffn(h, p["moe"], top_k=cfg.top_k,
                                       capacity_factor=cfg.capacity_factor,
                                       act=cfg.act)
    else:
        mlp_out = L.mlp(h, p["mlp"], act=cfg.act)
    if gemma:
        mlp_out = L.rms_norm(mlp_out, p["ln_mlp_post"], cfg.norm_eps)
    x = x + mlp_out
    return x, aux, (kv if collect_kv else None)


def group_fwd(x, gp, cfg: ModelConfig, shared: dict, *,
              image_embeds=None, collect_kv: bool = False, mesh=None,
              dp_axes=("data",)):
    """One group forward. Returns (x, aux_loss, cache_dict).

    cache_dict (when collect_kv) uses the same keys as group_cache() so a
    prefill can hand its stacked ys directly to the decoder.
    """
    lay = group_layout(cfg)
    aux_total = 0.0
    kvs: dict[str, Any] = {}
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    for i, sub in enumerate(lay["subs"]):
        name = f"{sub}_{i}"
        if sub in ("attn", "attn_global", "attn_local"):
            window = cfg.window if sub == "attn_local" else 0
            x, aux, kv = _tf_layer_fwd(
                x, gp[name], cfg, window=window,
                softcap=cfg.softcap_attn or None, collect_kv=collect_kv,
                mesh=mesh, dp_axes=dp_axes)
            aux_total += aux
            if collect_kv:
                # store (B, Hkv, S, D) — the decode cache layout (§Perf B5)
                kvs[f"k_{name}"] = jnp.swapaxes(kv[0], 1, 2)
                kvs[f"v_{name}"] = jnp.swapaxes(kv[1], 1, 2)
        elif sub == "cross":
            t_img = image_embeds.shape[1]
            kimg = (image_embeds @ gp[name]["attn"]["wk"]).reshape(
                b, t_img, cfg.n_kv_heads, hd)
            vimg = (image_embeds @ gp[name]["attn"]["wv"]).reshape(
                b, t_img, cfg.n_kv_heads, hd)
            x, aux, _ = _tf_layer_fwd(
                x, gp[name], cfg, kv_override=(kimg, vimg), causal=False)
            aux_total += aux
            if collect_kv:
                kvs["k_cross"] = jnp.swapaxes(kimg, 1, 2)
                kvs["v_cross"] = jnp.swapaxes(vimg, 1, 2)
        elif sub == "mlstm":
            h = L.rms_norm(x, gp[name]["ln"], cfg.norm_eps)
            y = XL.mlstm_block(h, gp[name]["core"], n_heads=cfg.n_heads,
                               chunk=cfg.ssm_chunk, return_state=collect_kv)
            if collect_kv:
                y, st = y
                (kvs[f"C_{name}"], kvs[f"n_{name}"],
                 kvs[f"m_{name}"]) = st
            x = x + y
        elif sub == "slstm":
            h = L.rms_norm(x, gp[name]["ln"], cfg.norm_eps)
            # §Perf C1/C2: sequential recurrence runs inside shard_map
            y = XL.slstm_block(h, gp[name]["core"], n_heads=cfg.n_heads,
                               return_state=collect_kv, mesh=mesh,
                               dp_axes=dp_axes)
            if collect_kv:
                y, st = y
                (kvs[f"c_{name}"], kvs[f"n_{name}"], kvs[f"h_{name}"],
                 kvs[f"m_{name}"]) = st
            x = x + y
        elif sub == "mamba":
            h = L.rms_norm(x, gp[name]["ln"], cfg.norm_eps)
            y = M2.mamba2_block(h, gp[name]["core"], n_heads=cfg.n_heads,
                                d_state=cfg.ssm_state, chunk=cfg.ssm_chunk,
                                expand=cfg.ssm_expand,
                                return_state=collect_kv)
            if collect_kv:
                y, st = y
                kvs[f"ssm_{name}"] = st
            x = x + y
        elif sub == "shared":
            x, aux, kv = _tf_layer_fwd(
                x, shared["shared_attn"], cfg, collect_kv=collect_kv)
            aux_total += aux
            if collect_kv:
                kvs[f"k_shared_{i}"] = jnp.swapaxes(kv[0], 1, 2)
                kvs[f"v_shared_{i}"] = jnp.swapaxes(kv[1], 1, 2)
    return x, aux_total, kvs


# ---------------------------------------------------------------------------
# decode — group body (single token, recurrent/cached)
# ---------------------------------------------------------------------------
def _attn_decode(x, p, cfg: ModelConfig, cache_k, cache_v, pos, length, *,
                 window=0, softcap=None, mode="far", mesh=None,
                 dp_axes=("data",), kv_override_cache=None):
    """Single-token attention against a cache.

    x: (B, 1, d). cache_k/v: (B, Hkv, S_max, Dh) — stored PRE-TRANSPOSED
    (§Perf B5) so the QK^T and PV dots consume the cache directly; the
    (B,S,H,D) layout cost a full transpose-copy of the cache per layer per
    step. Returns (out, ck, cv).
    mode: far (shard_map push-down) | naive (shard_map fetch) |
          local (heads-TP, GSPMD only).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    h = L.rms_norm(x, p["ln_attn"], cfg.norm_eps)
    q = (h @ p["attn"]["wq"]).reshape(b, 1, cfg.n_heads, hd)
    pos_arr = jnp.full((b, 1), pos, jnp.int32)
    q = L.rope(q, pos_arr, theta=cfg.rope_theta)[:, 0]        # (B, Hq, Dh)
    append = kv_override_cache is None
    if append:
        k_new = (h @ p["attn"]["wk"]).reshape(b, 1, cfg.n_kv_heads, hd)
        v_new = (h @ p["attn"]["wv"]).reshape(b, 1, cfg.n_kv_heads, hd)
        k_new = L.rope(k_new, pos_arr, theta=cfg.rope_theta)
        k_row = jnp.swapaxes(k_new, 1, 2)       # (B, Hkv, 1, Dh)
        v_row = jnp.swapaxes(v_new, 1, 2)
        glen = jnp.maximum(length, pos + 1) * jnp.ones((b,), jnp.int32)
    else:
        cache_k, cache_v = kv_override_cache
        glen = length * jnp.ones((b,), jnp.int32)

    if window and window > 0:
        lo = jnp.maximum(0, pos + 1 - window)
    else:
        lo = 0

    scale = 1.0 / math.sqrt(hd)

    if mode in ("far", "naive") and mesh is not None:
        from repro.core import far_kv
        from jax.sharding import PartitionSpec as P

        def sm(qr, kn, vn, ck, cv, gl, lo_):
            # ck/cv: (B_loc, Hkv, S_loc, Dh) — this device's pool shard.
            b_loc = ck.shape[0]
            s_loc = ck.shape[2]
            start = jax.lax.axis_index("model") * s_loc
            if append:
                # §Perf B3: the append touches exactly ONE cache row on the
                # owning shard (predicated 1-row DUS). Appending at the
                # GSPMD level instead made the partitioner rewrite the
                # whole local slice through a masked select every step.
                off = jnp.clip(pos - start, 0, s_loc - 1)
                in_range = (pos >= start) & (pos < start + s_loc)
                cur_k = jax.lax.dynamic_slice(
                    ck, (0, 0, off, 0), (b_loc, ck.shape[1], 1, hd))
                cur_v = jax.lax.dynamic_slice(
                    cv, (0, 0, off, 0), (b_loc, cv.shape[1], 1, hd))
                row_k = jnp.where(in_range, kn.astype(ck.dtype), cur_k)
                row_v = jnp.where(in_range, vn.astype(cv.dtype), cur_v)
                ck = jax.lax.dynamic_update_slice(ck, row_k, (0, 0, off, 0))
                cv = jax.lax.dynamic_update_slice(cv, row_v, (0, 0, off, 0))
            if mode == "naive":
                # RCPU: fetch raw KV rows, then attend locally
                ckf = jax.lax.all_gather(ck, "model", axis=2, tiled=True)
                cvf = jax.lax.all_gather(cv, "model", axis=2, tiled=True)
                o, m, l = _partial_attention_window(
                    qr, ckf, cvf, gl, lo_, 0, scale, softcap)
                return (o / jnp.maximum(l, 1e-30)[..., None], ck, cv)
            # FV: partials at the shard owner, ship only (o, m, l)
            o, m, l = _partial_attention_window(
                qr, ck, cv, gl, lo_, start, scale, softcap)
            return (far_kv.merge_partials_named(o, m, l, "model"), ck, cv)

        lo_arr = lo * jnp.ones((b,), jnp.int32)
        kn = k_row if append else jnp.zeros((b, cache_k.shape[1], 1, hd),
                                            cache_k.dtype)
        vn = v_row if append else kn
        # check_vma=False: the naive path's all_gather output is replicated
        # over "model" mathematically but not statically inferable.
        attn, cache_k, cache_v = shard_map(
            sm, mesh=mesh,
            in_specs=(P(dp_axes), P(dp_axes), P(dp_axes),
                      P(dp_axes, None, "model"), P(dp_axes, None, "model"),
                      P(dp_axes), P(dp_axes)),
            out_specs=(P(dp_axes), P(dp_axes, None, "model"),
                       P(dp_axes, None, "model")),
            check_vma=False)(q, kn, vn, cache_k, cache_v, glen, lo_arr)
    else:
        # local/GSPMD path: plain masked attention over the whole cache
        # (same MXU-native dtype discipline as the far path — no f32 cache
        # copies; see _partial_attention_window)
        if append:
            cache_k = jax.lax.dynamic_update_slice(
                cache_k, k_row.astype(cache_k.dtype), (0, 0, pos, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache_v, v_row.astype(cache_v.dtype), (0, 0, pos, 0))
        s_max = cache_k.shape[2]
        kpos = jnp.arange(s_max)
        valid = (kpos[None] < glen[:, None]) & (kpos[None] >= lo)
        g = cfg.n_heads // cfg.n_kv_heads
        qc = q.astype(cache_k.dtype).reshape(b, cfg.n_kv_heads, g, hd)
        scores = jnp.einsum("bhgd,bhsd->bhgs", qc, cache_k, optimize=True,
                            preferred_element_type=jnp.float32) * scale
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        scores = jnp.where(valid[:, None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhgs,bhsd->bhgd", w.astype(cache_v.dtype),
                          cache_v, optimize=True,
                          preferred_element_type=jnp.float32)
        attn = attn.reshape(b, cfg.n_heads, hd)

    out = attn.reshape(b, -1).astype(x.dtype) @ p["attn"]["wo"]
    if cfg.attn_pattern == "gemma2_alt":
        out = L.rms_norm(out, p["ln_attn_post"], cfg.norm_eps)
    x = x + out[:, None]
    # mlp
    h = L.rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    if "moe" in p:
        # decode has few tokens; generous capacity avoids routing drops
        mlp_out, _ = MOE.moe_ffn(h, p["moe"], top_k=cfg.top_k,
                                 capacity_factor=4.0, act=cfg.act)
    else:
        mlp_out = L.mlp(h, p["mlp"], act=cfg.act)
    if cfg.attn_pattern == "gemma2_alt":
        mlp_out = L.rms_norm(mlp_out, p["ln_mlp_post"], cfg.norm_eps)
    x = x + mlp_out
    return x, cache_k, cache_v


def _partial_attention_window(q, k, v, glen, lo, start, scale, softcap=None):
    """partial_attention with a lower-bound position mask (sliding window).

    k/v: (B, Hkv, S, Dh) — the §Perf B5 pre-transposed cache layout, so the
    dots consume the cache with no transpose copy.

    MXU-native numerics: QK^T and PV consume the cache in its STORED dtype
    (bf16 on the wire) with f32 accumulation via preferred_element_type —
    never materializing an f32 copy of the cache slice. §Perf B1: the f32
    `.astype` copies made XLA carry an f32 scan accumulator for the whole
    stacked cache (6 full-cache HBM passes per decode step instead of 1).
    """
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    qc = q.astype(k.dtype).reshape(b, hkv, g, d)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qc, k, optimize=True,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    kpos = start + jnp.arange(s)
    valid = (kpos[None] < glen[:, None]) & (kpos[None] >= lo[:, None])
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    m = jnp.max(scores, axis=-1)
    p = jnp.where(valid[:, None, None], jnp.exp(scores - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p.astype(k.dtype), v, optimize=True,
                   preferred_element_type=jnp.float32)
    return (o.reshape(b, hq, d), m.reshape(b, hq), l.reshape(b, hq))


def group_dec(x, gp, cache, cfg: ModelConfig, shared: dict, pos, length, *,
              mode="far", mesh=None, dp_axes=("data",)):
    """Single-token decode through one group. cache: dict of per-sub states."""
    lay = group_layout(cfg)
    new_cache = dict(cache)
    for i, sub in enumerate(lay["subs"]):
        name = f"{sub}_{i}"
        if sub in ("attn", "attn_global", "attn_local"):
            window = cfg.window if sub == "attn_local" else 0
            x, ck, cv = _attn_decode(
                x, gp[name], cfg, cache[f"k_{name}"], cache[f"v_{name}"],
                pos, length, window=window,
                softcap=cfg.softcap_attn or None, mode=mode, mesh=mesh,
                dp_axes=dp_axes)
            new_cache[f"k_{name}"] = ck
            new_cache[f"v_{name}"] = cv
        elif sub == "cross":
            x, _, _ = _attn_decode(
                x, gp[name], cfg, cache["k_cross"], cache["v_cross"],
                pos, cfg.n_image_tokens, mode="local", mesh=mesh,
                dp_axes=dp_axes,
                kv_override_cache=(cache["k_cross"], cache["v_cross"]))
        elif sub == "mlstm":
            h = L.rms_norm(x, gp[name]["ln"], cfg.norm_eps)
            y, st = XL.mlstm_decode_step(
                h[:, 0], gp[name]["core"],
                (cache[f"C_{name}"], cache[f"n_{name}"], cache[f"m_{name}"]),
                n_heads=cfg.n_heads)
            x = x + y[:, None]
            (new_cache[f"C_{name}"], new_cache[f"n_{name}"],
             new_cache[f"m_{name}"]) = st
        elif sub == "slstm":
            h = L.rms_norm(x, gp[name]["ln"], cfg.norm_eps)
            y, st = XL.slstm_decode_step(
                h[:, 0], gp[name]["core"],
                (cache[f"c_{name}"], cache[f"n_{name}"],
                 cache[f"h_{name}"], cache[f"m_{name}"]),
                n_heads=cfg.n_heads)
            x = x + y[:, None]
            (new_cache[f"c_{name}"], new_cache[f"n_{name}"],
             new_cache[f"h_{name}"], new_cache[f"m_{name}"]) = st
        elif sub == "mamba":
            h = L.rms_norm(x, gp[name]["ln"], cfg.norm_eps)
            y, st = M2.mamba2_decode_step(
                h[:, 0], gp[name]["core"], cache[f"ssm_{name}"],
                n_heads=cfg.n_heads, d_state=cfg.ssm_state,
                expand=cfg.ssm_expand)
            x = x + y[:, None]
            new_cache[f"ssm_{name}"] = st
        elif sub == "shared":
            x, ck, cv = _attn_decode(
                x, shared["shared_attn"], cfg, cache[f"k_shared_{i}"],
                cache[f"v_shared_{i}"], pos, length, mode=mode, mesh=mesh,
                dp_axes=dp_axes)
            new_cache[f"k_shared_{i}"] = ck
            new_cache[f"v_shared_{i}"] = cv
    return x, new_cache


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------
def group_cache(cfg: ModelConfig, batch: int, max_seq: int,
                kv_dtype=jnp.bfloat16) -> dict:
    lay = group_layout(cfg)
    hd = cfg.resolved_head_dim
    c: dict = {}
    for i, sub in enumerate(lay["subs"]):
        name = f"{sub}_{i}"
        if sub in ("attn", "attn_global", "attn_local"):
            # (B, Hkv, S, Dh): pre-transposed for the decode dots (§Perf B5)
            shape = (batch, cfg.n_kv_heads, max_seq, hd)
            c[f"k_{name}"] = jnp.zeros(shape, kv_dtype)
            c[f"v_{name}"] = jnp.zeros(shape, kv_dtype)
        elif sub == "cross":
            shape = (batch, cfg.n_kv_heads, cfg.n_image_tokens, hd)
            c["k_cross"] = jnp.zeros(shape, kv_dtype)
            c["v_cross"] = jnp.zeros(shape, kv_dtype)
        elif sub == "mlstm":
            C, n, m = XL.mlstm_init_state(batch, cfg.d_model, cfg.n_heads)
            c[f"C_{name}"], c[f"n_{name}"], c[f"m_{name}"] = C, n, m
        elif sub == "slstm":
            cc, n, h, m = XL.slstm_init_state(batch, cfg.d_model, cfg.n_heads)
            (c[f"c_{name}"], c[f"n_{name}"], c[f"h_{name}"],
             c[f"m_{name}"]) = cc, n, h, m
        elif sub == "mamba":
            c[f"ssm_{name}"] = M2.mamba2_init_state(
                batch, cfg.d_model, cfg.n_heads, cfg.ssm_state,
                expand=cfg.ssm_expand)
        elif sub == "shared":
            shape = (batch, cfg.n_kv_heads, max_seq, hd)
            c[f"k_shared_{i}"] = jnp.zeros(shape, kv_dtype)
            c[f"v_shared_{i}"] = jnp.zeros(shape, kv_dtype)
    return c
