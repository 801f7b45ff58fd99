"""Distribution tests on a forced 8-device CPU mesh (own process group).

Run in a subprocess so XLA_FLAGS=--xla_force_host_platform_device_count=8
doesn't leak into other tests (jax locks device count at first init).
"""
import json
import os
import subprocess
import sys
import textwrap


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str) -> dict:
    """Run `body` (python source) with 8 forced CPU devices; the script must
    print a single JSON line starting with RESULT:."""
    src = ("import os\n"
           "os.environ['XLA_FLAGS']="
           "'--xla_force_host_platform_device_count=8'\n"
           + textwrap.dedent(body))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT line in: {out.stdout[-2000:]}")


def test_far_naive_local_equivalence():
    """FV == RCPU == LCPU decode logits on a (2,4) mesh (paper's triad)."""
    res = run_in_subprocess("""
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import smoke_config
    from repro.models.lm import LM
    from repro.launch.mesh import make_test_mesh, set_mesh

    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh((2, 4), ("data", "model"))
    cfg = smoke_config(get_config("granite-3-2b"))
    lm_far = LM(cfg, mesh=mesh, dp_axes=("data",))
    lm_loc = LM(cfg)
    params = lm_far.init(key)
    B, MAX_S = 4, 128
    toks = jax.random.randint(key, (B, 1), 0, cfg.vocab)
    outs = {}
    with set_mesh(mesh):
        for mode, lm in [("far", lm_far), ("naive", lm_far),
                         ("local", lm_loc)]:
            c = lm.init_cache(B, MAX_S, jnp.float32)
            lg, c = lm.decode_step(params, c, {"tokens": toks},
                                   jnp.int32(0), jnp.int32(0), mode=mode)
            lg, c = lm.decode_step(params, c, {"tokens": toks},
                                   jnp.int32(1), jnp.int32(1), mode=mode)
            outs[mode] = np.asarray(lg[:, -1])
    e_fn = float(np.max(np.abs(outs["far"] - outs["naive"])))
    e_fl = float(np.max(np.abs(outs["far"] - outs["local"])))
    print("RESULT:" + json.dumps({"far_naive": e_fn, "far_local": e_fl}))
    """)
    assert res["far_naive"] < 2e-4
    assert res["far_local"] < 2e-4


def test_sharded_train_step_matches_single_device():
    """One GSPMD train step on (2,2,2) pod mesh == unsharded step."""
    res = run_in_subprocess("""
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.configs.base import TrainConfig, smoke_config
    from repro.models.lm import LM
    from repro.launch.mesh import make_test_mesh, set_mesh
    from repro.distributed import sharding as S
    from repro.runtime import steps as R

    cfg = smoke_config(get_config("granite-3-2b")).replace(remat=False)
    key = jax.random.PRNGKey(0)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1)
    B, SQ = 8, 32
    batch = {"tokens": jax.random.randint(key, (B, SQ), 0, cfg.vocab),
             "labels": jax.random.randint(key, (B, SQ), 0, cfg.vocab)}

    # single-device reference
    lm0 = LM(cfg)
    params0 = lm0.init(key)
    step0 = jax.jit(R.make_train_step(lm0, tcfg))
    opt0 = R.init_train_state(lm0, tcfg, params0)
    p0, o0, m0 = step0(params0, opt0, batch)

    # sharded: multi-pod style mesh (2,2,2)
    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    lm = LM(cfg, mesh=mesh)
    pspecs = S.param_specs(jax.eval_shape(lm.init, key), mesh, cfg)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    params = jax.device_put(params0, psh)
    opt = R.init_train_state(lm, tcfg, params)
    bspecs = S.batch_specs(cfg, type("S", (), {
        "kind": "train", "seq_len": SQ, "global_batch": B})(), mesh)
    bsh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
    batch_sh = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    step = jax.jit(R.make_train_step(lm, tcfg))
    with set_mesh(mesh):
        p1, o1, m1 = step(params, opt, batch_sh)

    dloss = abs(float(m0["loss"]) - float(m1["loss"]))
    # param drift between the two runs
    da = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)))
    print("RESULT:" + json.dumps({"dloss": dloss, "dparam": da}))
    """)
    assert res["dloss"] < 5e-3
    assert res["dparam"] < 5e-2   # adam eps-scale differences only


def test_grad_accumulation_equivalence():
    """microbatched train step == full-batch step (grad accumulation)."""
    res = run_in_subprocess("""
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import TrainConfig, smoke_config
    from repro.models.lm import LM
    from repro.runtime import steps as R

    cfg = smoke_config(get_config("granite-3-2b")).replace(
        remat=False, param_dtype="float32")
    key = jax.random.PRNGKey(0)
    lm = LM(cfg)
    params = lm.init(key)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
             "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
    s1 = jax.jit(R.make_train_step(lm, tcfg, microbatches=1))
    s4 = jax.jit(R.make_train_step(lm, tcfg, microbatches=4))
    o1 = R.init_train_state(lm, tcfg, params)
    o4 = R.init_train_state(lm, tcfg, params)
    p1, _, m1 = s1(params, o1, batch)
    p4, _, m4 = s4(params, o4, batch)
    dloss = abs(float(m1["loss"]) - float(m4["loss"]))
    dp = max(float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
    print("RESULT:" + json.dumps({"dloss": dloss, "dparam": dp}))
    """)
    assert res["dloss"] < 1e-4
    assert res["dparam"] < 1e-4


def test_dryrun_single_cell_and_hlo_analysis():
    """The dry-run machinery itself: lower+compile one small cell on the
    512-device production mesh and check the roofline record is complete."""
    res = run_in_subprocess("""
    import json
    import os
    os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=512'
    from repro.launch import dryrun as D
    rec = D.run_cell("xlstm-125m", "decode_32k", "pod")
    rec["roofline"] = D.roofline_terms(rec)
    out = {"status": rec["status"], "n_chips": rec["n_chips"],
           "dom": rec["roofline"]["dominant"],
           "has_terms": all(k in rec["roofline"] for k in
                            ("t_compute_s", "t_memory_s",
                             "t_collective_s"))}
    print("RESULT:" + json.dumps(out))
    """)
    assert res["status"] == "ok"
    assert res["n_chips"] == 256
    assert res["has_terms"]


def test_hlo_analyzer_trip_scaling():
    """while-loop bodies scale by trip count (raw cost_analysis doesn't)."""
    res = run_in_subprocess("""
    import json
    import jax, jax.numpy as jnp
    from repro.launch.hlo_analysis import analyze
    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    compiled = jax.jit(scanned).lower(x, w).compile()
    a = analyze(compiled.as_text())
    raw = compiled.cost_analysis()["flops"]
    print("RESULT:" + json.dumps({"scaled": a["flops"], "raw": raw}))
    """)
    expect = 10 * 2 * 256 ** 3
    assert abs(res["scaled"] / expect - 1.0) < 0.05
    assert res["raw"] < expect / 5          # documents the undercount
