import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices stand in for 2 pods x 256 v5e chips.
For each cell we:

  1. build the production mesh (16,16) ("data","model") or (2,16,16)
     ("pod","data","model"),
  2. derive param/opt/batch/cache PartitionSpecs (distributed/sharding.py),
  3. jit the exact step function the runtime executes (runtime/steps.py)
     against ShapeDtypeStruct stand-ins (no allocation),
  4. .lower().compile() — sharding mismatches, compile-time OOM, and
     unsupported collectives all fail HERE,
  5. record memory_analysis(), cost_analysis(), and the collective-op bytes
     parsed from the compiled HLO into launch_results/<cell>.json —
     the §Roofline analysis reads these.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multipod  # 512-chip
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCHS, SHAPES, cell_is_applicable, get_config,
                           input_specs)
from repro.configs.base import ModelConfig, Shape, TrainConfig
from repro.distributed import sharding as S
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.mesh import HW, make_production_mesh
from repro.models.lm import LM
from repro.runtime import steps as R

RESULT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "launch_results")

# HLO collective ops whose operand bytes constitute the collective roofline
# term (paper: "bytes over the network"; here: bytes over ICI/DCN links).
# Compiled HLO references operands by %name, so operand bytes are derived
# from the op's RESULT shape + op kind + replica-group size:
#   all-reduce / all-to-all / collective-permute: operand == result
#   all-gather:      operand = result / group_size
#   reduce-scatter:  operand = result * group_size
_COLL_LINE_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+?)\}")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)           # [n_groups,group_size]<=[...]
    if m:
        return max(1, int(m.group(2)))
    m = _GROUPS_LIST_RE.search(line)      # {{0, 1, ...}, ...}
    if m:
        return max(1, len(m.group(1).split(",")))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective op class (operand sizes).

    The compiled module is the post-SPMD per-device program, so shapes are
    per-partition: summing operand bytes gives bytes each chip injects into
    the interconnect per step. `link_bytes` additionally models ring-
    algorithm link traffic: all-reduce moves 2x(g-1)/g of the operand,
    all-gather/reduce-scatter (g-1)/g of the full tensor, a2a/permute 1x.
    """
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    link = 0.0
    n_ops = 0
    for line in hlo_text.splitlines():
        m = _COLL_LINE_RE.search(line)
        if not m:
            continue
        dt, dims, op = m.groups()
        res = _shape_bytes(dt, dims)
        g = _group_size(line)
        if op == "all-gather":
            operand = res // max(g, 1)
            link += res * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            operand = res * g
            link += operand * (g - 1) / max(g, 1)
        elif op == "all-reduce":
            operand = res
            link += 2.0 * res * (g - 1) / max(g, 1)
        else:  # all-to-all, collective-permute
            operand = res
            link += res
        out[op] += operand
        n_ops += 1
    out["total"] = sum(v for k, v in out.items())
    out["link_bytes"] = int(link)
    out["n_ops"] = n_ops
    return out


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    keep = ("flops", "bytes accessed", "transcendentals", "optimal_seconds")
    return {k: float(v) for k, v in ca.items() if k in keep}


# ---------------------------------------------------------------------------
# model-FLOPs estimate (6 * N_active * D) for the useful-compute ratio
# ---------------------------------------------------------------------------
def exact_param_counts(cfg: ModelConfig) -> tuple[int, int]:
    """(total, active) param counts measured from the real init tree.

    Total comes from jax.eval_shape over LM.init (no allocation). Active
    subtracts the un-routed expert weights for MoE: per token only top_k of
    n_experts expert FFNs run.
    """
    lm = LM(cfg)
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    active = total
    if cfg.n_experts:
        expert_w = 3 * cfg.d_model * cfg.d_expert * cfg.n_experts
        active_w = 3 * cfg.d_model * cfg.d_expert * cfg.top_k
        active = total - cfg.n_layers * (expert_w - active_w)
    return total, active


def model_flops(cfg: ModelConfig, shape: Shape, n_total: int,
                n_active: int) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (fwd-only).

    Embedding-table params don't do matmul FLOPs on the input side, but the
    unembedding does; we follow the standard convention and count all
    non-embedding params + the unembed projection.
    """
    emb = cfg.vocab * cfg.d_model          # input embedding (lookup, no FLOPs)
    n_eff = max(n_active - emb, 1)
    if shape.kind == "train":
        return 6.0 * n_eff * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n_eff * shape.seq_len * shape.global_batch
    return 2.0 * n_eff * shape.global_batch  # decode: 1 new token


# ---------------------------------------------------------------------------
# cell runners
# ---------------------------------------------------------------------------
def build_step(cfg: ModelConfig, shape: Shape, mesh, *, kv_mode: str = "far",
               microbatches: int = 1, remat: bool | None = None):
    """Returns (jitted_fn, arg ShapeDtypeStructs tuple)."""
    if remat is not None:
        cfg = cfg.replace(remat=remat)
    dp = S.batch_axes(mesh, shape.global_batch)
    act = S.activation_spec(mesh, shape.global_batch)
    lm = LM(cfg, mesh=mesh, dp_axes=dp,
            act_spec=NamedSharding(mesh, act),
            ce_act_spec=NamedSharding(mesh, act))
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lm.init, key)
    pspecs = S.param_specs(pshapes, mesh, cfg)
    psharding = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    bspecs = S.batch_specs(cfg, shape, mesh)
    bshard = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
    ispecs = input_specs(cfg, shape)
    ispecs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=bshard[k])
              for k, v in ispecs.items()}
    pargs = jax.tree.map(
        lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        pshapes, psharding)

    if shape.kind == "train":
        tcfg = TrainConfig(microbatch=microbatches)
        step = R.make_train_step(lm, tcfg, microbatches=microbatches)
        oshapes = jax.eval_shape(lambda p: R.init_train_state(lm, tcfg, p),
                                 pshapes)
        ospecs = {"adam": {"m": pspecs, "v": pspecs, "step": P()}}
        oshard = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)
        oargs = jax.tree.map(
            lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype,
                                                sharding=sh),
            oshapes, oshard)
        jitted = jax.jit(step,
                         in_shardings=(psharding, oshard, bshard),
                         donate_argnums=(0, 1))
        return jitted, (pargs, oargs, ispecs)

    if shape.kind == "prefill":
        step = R.make_prefill_step(lm, max_seq=shape.seq_len)
        jitted = jax.jit(step, in_shardings=(psharding, bshard))
        return jitted, (pargs, ispecs)

    # decode
    step = R.make_serve_step(lm, mode=kv_mode)
    cshapes = jax.eval_shape(
        lambda: lm.init_cache(shape.global_batch, shape.seq_len,
                              jnp.bfloat16))
    cspecs = S.cache_specs(cshapes, mesh, shape.global_batch)
    cshard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)
    cargs = jax.tree.map(
        lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        cshapes, cshard)
    scal = jax.ShapeDtypeStruct((), jnp.int32)
    jitted = jax.jit(step,
                     in_shardings=(psharding, cshard, bshard, None, None),
                     donate_argnums=(1,))
    return jitted, (pargs, cargs, ispecs, scal, scal)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             kv_mode: str = "far", tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "kv_mode": kv_mode, "tag": tag}
    ok, why = cell_is_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    jitted, args = build_step(cfg, shape, mesh, kv_mode=kv_mode)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)          # raw, body-once (reference)
    cost = _cost_dict(compiled)           # raw cost_analysis (reference)
    mem = _mem_dict(compiled)
    # trip-count-scaled per-device analysis (the real roofline input):
    # cost_analysis counts while bodies once; this scales by trip count.
    scaled = hlo_analyze(hlo)

    total_p, act_p = exact_param_counts(cfg)
    rec.update(
        status="ok", n_chips=n_chips,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        cost=cost, memory=mem, collectives=coll, scaled=scaled,
        params_total=total_p, params_active=act_p,
        model_flops=model_flops(cfg, shape, total_p, act_p),
        hlo_bytes=len(hlo),
    )
    return rec


def roofline_terms(rec: dict) -> dict:
    """The three §Roofline terms (seconds) from one cell record."""
    if rec.get("status") != "ok":
        return {}
    sc = rec["scaled"]                      # trip-count-scaled, per device
    flops = sc["flops"]
    bytes_acc = sc["hbm_bytes"]
    coll = sc["collective_bytes"]
    t_compute = flops / HW["peak_flops_bf16"]
    t_memory = bytes_acc / HW["hbm_bw"]
    t_coll = coll / HW["ici_bw_per_link"]
    dom = max((t_compute, "compute"), (t_memory, "memory"),
              (t_coll, "collective"))[1]
    useful = rec["model_flops"] / max(flops * rec["n_chips"], 1.0)
    bound = max(t_compute, t_memory, t_coll)
    return {"t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dom,
            "useful_flops_ratio": useful,
            "roofline_fraction": t_compute / max(bound, 1e-30)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS + (None,))
    ap.add_argument("--shape", default=None, choices=tuple(SHAPES) + (None,))
    ap.add_argument("--mesh", default="both",
                    choices=("pod", "multipod", "both"))
    ap.add_argument("--kv-mode", default="far",
                    choices=("far", "naive", "local"))
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true",
                    help="re-run cells that already have a result file")
    ap.add_argument("--out-dir", default=RESULT_DIR)
    args = ap.parse_args()

    os.makedirs(args.out_dir, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = (["pod", "multipod"] if args.mesh == "both" else [args.mesh])

    n_ok = n_skip = n_fail = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                name = f"{arch}_{shape_name}_{mesh_kind}"
                if args.kv_mode != "far":
                    name += f"_{args.kv_mode}"
                if args.tag:
                    name += f"_{args.tag}"
                path = os.path.join(args.out_dir, name + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached ] {name}")
                    continue
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape_name, mesh_kind,
                                   kv_mode=args.kv_mode, tag=args.tag)
                    rec["roofline"] = roofline_terms(rec)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                dt = time.time() - t0
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "error"
                extra = ""
                if st == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']}"
                             f" tc={r['t_compute_s']:.3g}s"
                             f" tm={r['t_memory_s']:.3g}s"
                             f" tx={r['t_collective_s']:.3g}s")
                elif st == "error":
                    extra = " " + rec["error"][:120]
                print(f"[{st:7s}] {name} ({dt:.0f}s){extra}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")


if __name__ == "__main__":
    main()
