"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Prefill + decode loop against the disaggregated KV pool. --kv-mode picks the
paper's evaluation triad: far (FV push-down), naive (RCPU fetch), local
(LCPU heads-TP). Reports tokens/s and the modeled per-layer network bytes
for the chosen mode (the Fig. 8 economics applied to serving).

With --listen, the --pool-nodes count stops being a model: that many
`FViewServer` sockets are spun up and a `FarCluster` of
`RemoteNodeHandle`s (repro.net) runs a real verb round over them,
reporting MEASURED shipped/read bytes next to the modeled number.
--connect HOST:PORT[,...] does the same against already-running servers
(`python -m repro.net.server`); the endpoint count overrides
--pool-nodes. See docs/network.md.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--kv-mode", default="local",
                    choices=("far", "naive", "local"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--pool-nodes", type=int, default=16,
                    help="modeled Farview node count the KV pool is "
                         "sharded over (the tp term of the Fig. 8 "
                         "economics; mirrors FarCluster scale-out)")
    ap.add_argument("--listen", action="store_true",
                    help="self-host --pool-nodes FViewServer sockets and "
                         "route the pool round through FarCluster + "
                         "RemoteNodeHandle (real bytes, not modeled)")
    ap.add_argument("--connect", default=None,
                    metavar="HOST:PORT[,HOST:PORT...]",
                    help="running FViewServer endpoints to use as the "
                         "pool; the endpoint count overrides --pool-nodes")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.pool_nodes < 1:
        ap.error("--pool-nodes must be >= 1")
    if args.listen and args.connect:
        ap.error("--listen and --connect are mutually exclusive")
    from repro import compile_cache
    compile_cache.enable()

    from repro.configs import get_config
    from repro.configs.base import smoke_config
    from repro.core.far_kv import shipped_bytes_per_layer
    from repro.models import frontends as F
    from repro.models.lm import LM
    from repro.runtime.steps import make_serve_step

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    key = jax.random.PRNGKey(args.seed)
    lm = LM(cfg)
    params = lm.init(key)
    B = args.batch

    # prompt
    if cfg.embed_input:
        batch = {"embeds": F.audio_frame_embeddings(
            cfg, B, args.prompt_len, dtype=jnp.float32)}
    else:
        batch = {"tokens": jax.random.randint(
            key, (B, args.prompt_len), 0, cfg.vocab)}
    if cfg.n_image_tokens:
        batch["image_embeds"] = F.image_patch_embeddings(
            cfg, B, dtype=jnp.float32)

    serve = jax.jit(make_serve_step(lm, mode=args.kv_mode))
    cache = lm.init_cache(B, args.max_seq, jnp.float32)

    # teacher-forced "prefill" via decode steps (keeps the driver simple and
    # exercises the cache write path; lm.prefill is the batched alternative)
    pos = 0
    tok = (batch["tokens"][:, :1] if "tokens" in batch
           else jnp.zeros((B, 1), jnp.int32))
    t0 = time.perf_counter()
    for t in range(args.prompt_len):
        inp = ({"tokens": batch["tokens"][:, t:t + 1]}
               if "tokens" in batch else
               {"embeds": batch["embeds"][:, t:t + 1]})
        tok, cache = serve(params, cache, inp, jnp.int32(pos),
                           jnp.int32(pos))
        pos += 1
    gen = []
    for _ in range(args.gen_len):
        inp = ({"tokens": tok[:, None]} if not cfg.embed_input else
               {"embeds": jnp.zeros((B, 1, cfg.d_model), jnp.float32)})
        tok, cache = serve(params, cache, inp, jnp.int32(pos),
                           jnp.int32(pos))
        gen.append(np.asarray(tok))
        pos += 1
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    total_tokens = B * (args.prompt_len + args.gen_len)
    print(f"served {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / dt:.1f} tok/s, mode={args.kv_mode})")
    nodes = args.pool_nodes
    if args.connect:
        nodes = len(args.connect.split(","))
    ship = shipped_bytes_per_layer(
        args.kv_mode, batch=B, hq=cfg.n_heads, hkv=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, seq_len=args.max_seq,
        tp=nodes)
    print(f"modeled network bytes/layer/step @{nodes} pool nodes: {ship} "
          f"({max(1, ship // nodes)}/node)")

    if args.listen or args.connect:
        _network_pool_round(args, nodes)


def _network_pool_round(args, nodes: int) -> None:
    """The real thing behind the model: a FarCluster of RemoteNodeHandles
    over FViewServer sockets runs one selection round on a KV-shaped
    table and reports MEASURED wire bytes (docs/network.md)."""
    from repro.core import operators as op
    from repro.core.table import Column, FTable
    from repro.net import remote_cluster

    servers = []
    if args.connect:
        endpoints = []
        for spec in args.connect.split(","):
            host, _, port = spec.strip().rpartition(":")
            endpoints.append((host or "127.0.0.1", int(port)))
    else:
        from repro.net.server import FViewServer
        servers = [FViewServer.start_in_thread(node_id=i)
                   for i in range(nodes)]
        endpoints = [(s.host, s.port) for s in servers]

    try:
        cl = remote_cluster(endpoints)
        cqp = cl.open_connection()
        n = 4096
        cols = (Column("pos", "i32"), Column("k0"), Column("k1"),
                Column("v0"), Column("v1"))
        rng = np.random.default_rng(args.seed)
        ft = FTable("kv_blocks", cols, n_rows=n)
        words = ft.encode({
            "pos": np.arange(n, dtype=np.int32),
            **{c.name: rng.standard_normal(n).astype(np.float32)
               for c in cols[1:]}})
        ct = cl.alloc_table_mem(cqp, ft)
        cl.table_write(cqp, ct, words)
        pipe = (op.Select((op.Predicate("k0", ">", 1.0),)),)
        res = cl.farview_request(cqp, ct, pipe).finalize()
        print(f"real pool round over {len(endpoints)} FViewServer "
              f"socket(s): {res.count}/{n} rows matched, "
              f"shipped {res.shipped_bytes} B, read {res.read_bytes} B "
              f"({max(1, res.shipped_bytes // len(endpoints))} B/node)")
        cl.free_table_mem(cqp, ct)
    finally:
        for s in servers:
            s.stop_thread()


if __name__ == "__main__":
    main()
