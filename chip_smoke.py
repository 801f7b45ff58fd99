#!/usr/bin/env python3
"""Bring-up check: the Farview request path, end to end, on one TPU.

Stands up an in-process `FViewServer` and its client over a real localhost
socket, as `python -m repro.launch.serve --listen` does (threads, never
child processes). Loads an 8-attribute table of the paper's shape
(2^24 rows x 8 words = 512 MiB by default, made from --seed) into a pool
of several GiB, and answers a few queries of each verb through the
client: selection + projection, smart addressing, group-by with overflow
merge, small-table join, pre-decrypt + select, and a regex over a string
table. Every answer is checked against a plain numpy reference on the
same data. The select and group verbs then run again through a 2-node
`FarCluster` on the same chip, over all but the table's last row (cluster
tables stay under 2^24 rows); its scatter-gather merge must give answers
byte-identical to numpy's and, on the rows they share, to the server's.

Earlier lines report each check, the sizes, first-call seconds (compile
included) and warm seconds: one bring-up run, not a benchmark. The last
line, printed only when every check passed, is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Exits non-zero without it when JAX finds no TPU (there is no CPU
fallback), when the repo's `src/` is not beside this script, or when any
check fails.

    python3 chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
FRAME = 1 << 30             # wire frame bound: whole-table writes ship whole
KEY, NONCE = (21, 42), 99   # at-rest cipher of the encrypted copy
N_BUCKETS = 1024
PATTERN = "err(or|no)[0-9]+x"
LOG_ROWS = 24               # word table rows = 2**LOG_ROWS, 8 words each
POOL_GIB = 4                # the server's pool; each cluster node gets half
STRING_ROWS = 1 << 20       # regex table: 32-byte strings


def log(msg: str) -> None:
    print(msg, flush=True)


def die(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"check {name}: {'pass' if ok else 'FAIL'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)


def timed(fn, warm: int = 2):
    """(first result, first-call s, best warm s). The first call compiles."""
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(warm):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return out, first, best


# ------------------------------------------------------------------ data
def bucket_of(keys: np.ndarray, n_buckets: int) -> np.ndarray:
    """kernels/ref.py's Fibonacci hash, in numpy."""
    h = keys.astype(np.uint32) * np.uint32(0x9E3779B1)
    return (h >> np.uint32(32 - int(np.log2(n_buckets)))).astype(np.int32)


def make_words(rng, n: int):
    """8 attributes: c0 a group/join key over 101 values (one crafted to
    share a hash bucket with another, so grouping overflows), c1..c3
    small integers (exact sums), c4..c7 uniform floats (predicates)."""
    keys = rng.choice(1 << 20, size=100, replace=False).astype(np.int32)
    cand = np.arange(1 << 20, 1 << 21, dtype=np.int32)
    clash = cand[bucket_of(cand, N_BUCKETS) == bucket_of(keys[:1],
                                                          N_BUCKETS)[0]]
    keys = np.append(keys, clash[0])
    words = np.empty((n, 8), np.float32)
    words[:, 0] = keys[rng.integers(0, len(keys), n)]
    words[:, 1:4] = rng.integers(0, 16, (n, 3))
    words[:, 4:] = rng.random((n, 4), dtype=np.float32)
    return words, keys


def make_strings(rng, n: int, width: int = 32):
    lens = rng.integers(16, width + 1, n).astype(np.int32)
    mat = rng.integers(97, 123, (n, width)).astype(np.uint8)
    hit = np.nonzero(rng.random(n) < 0.1)[0]
    tokens = [b"error7x", b"errno42x", b"error"]     # the last never matches
    for i in hit:
        tok = tokens[i % 3]
        at = int(rng.integers(0, lens[i] - len(tok) + 1))
        mat[i, at:at + len(tok)] = np.frombuffer(tok, np.uint8)
    mat[np.arange(width)[None, :] >= lens[:, None]] = 0
    return mat, lens


# ------------------------------------------------------------- references
def ref_select(words, mask, proj=None, extra=None):
    """Packed response rows: survivors first, dropped columns zeroed."""
    rows = words[mask]
    if proj is not None:
        rows = np.where(proj[None, :], rows, 0).astype(np.float32)
    if extra is not None:
        rows = np.concatenate([rows, extra], axis=1)
    out = np.zeros((len(words), rows.shape[1]), np.float32)
    out[: len(rows)] = rows
    return out


def ref_groups(keys, vals):
    """{key: (count, sum, min, max)} by sorting, exact for these data."""
    order = np.argsort(keys, kind="stable")
    k, v = keys[order], vals[order].astype(np.float64)
    start = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    return {int(key): (int(c), s, mn, mx) for key, c, s, mn, mx in zip(
        k[start], np.diff(np.r_[start, len(k)]),
        np.add.reduceat(v, start), np.minimum.reduceat(v, start),
        np.maximum.reduceat(v, start))}


def same_rows(got, want) -> bool:
    got = np.asarray(got, np.float32)
    return got.shape == want.shape and np.array_equal(
        got.view(np.uint32), want.view(np.uint32))


def same_groups(got: dict, want: dict) -> bool:
    """Same keys, and per key the same count and exactly equal sum, min
    and max vectors."""
    if set(got) != set(want):
        return False
    for key, (c, *aggs) in want.items():
        g_c, *g_aggs = got[key]
        if int(g_c) != int(c) or not all(
                np.array_equal(np.asarray(a, np.float64),
                               np.asarray(b, np.float64))
                for a, b in zip(g_aggs, aggs)):
            return False
    return True


# ------------------------------------------------------------------ main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        die(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        die(f"no TPU found: JAX's first device is {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    import jax.numpy as jnp

    from repro import compile_cache
    from repro.core import client as fv
    from repro.core import operators as op
    from repro.core.cluster import FarCluster
    from repro.core.table import INT_EXACT_LIMIT, Column, FTable
    from repro.kernels import ref as kref
    from repro.net.client import RemoteNodeHandle
    from repro.net.server import FViewServer

    cache_dir = compile_cache.enable()
    check = Checks()
    n = 1 << LOG_ROWS
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    words, keys = make_words(rng, n)
    mat, lens = make_strings(rng, STRING_ROWS)
    bkeys = rng.choice(keys, size=50, replace=False).astype(np.int32)
    bvals = rng.random((50, 2), dtype=np.float32)
    enc = kref.ctr_crypt(jnp.asarray(words.reshape(-1)).view(jnp.uint32),
                         jnp.asarray(KEY, jnp.uint32), NONCE)
    enc_words = np.asarray(enc).view(np.float32).reshape(words.shape)
    del enc
    log(f"device {device} | compile cache {cache_dir}")
    log(f"data: word table {n} rows x 8 words ({words.nbytes >> 20} MiB), "
        f"string table {len(mat)} rows x {mat.shape[1]} B, seed {args.seed}"
        f" ({time.perf_counter() - t0:.1f}s)")

    cols = (Column("c0", "i32"),) + tuple(Column(f"c{i}") for i in range(1, 8))
    pipes = {
        "select_project": [
            (op.Project(("c0", "c4", "c5")),
             op.Select((op.Predicate("c4", "<", 0.05),))),
            (op.Select((op.Predicate("c5", ">=", 0.97),
                        op.Predicate("c1", "==", 3.0))),)],
        "smart_address": [
            (op.SmartAddress(("c4", "c5", "c6")),
             op.Select((op.Predicate("c6", ">", 0.9),)))],
        "group_by": [
            (op.Select((op.Predicate("c4", "<", 0.5),)),
             op.GroupBy("c0", ("c1", "c2", "c3"),
                        aggs=("count", "sum", "min", "max"),
                        n_buckets=N_BUCKETS))],
        "join_small": [
            (op.Select((op.Predicate("c5", "<", 0.2),)),
             op.JoinSmall("c0", "dim", "k", ("v0", "v1")))],
        "pre_decrypt_select": [
            (op.Crypt(key=KEY, nonce=NONCE, when="pre"),
             op.Select((op.Predicate("c7", "<", 0.1),)))],
    }

    def expect(verb, pipe, words):
        """The numpy answer for one query (rows, or exact group dict)."""
        f = {f"c{i}": words[:, i] for i in range(8)}
        if verb == "select_project" and len(pipe) == 2:
            proj = np.isin(np.arange(8), [0, 4, 5])
            return ref_select(words, f["c4"] < np.float32(0.05), proj)
        if verb == "select_project":
            return ref_select(words, (f["c5"] >= np.float32(0.97))
                              & (f["c1"] == 3))
        if verb == "smart_address":
            return ref_select(words[:, 4:7], f["c6"] > np.float32(0.9))
        if verb == "group_by":
            m = f["c4"] < np.float32(0.5)
            return ref_groups(words[m, 0].astype(np.int32), words[m, 1:4])
        if verb == "join_small":
            order = np.argsort(bkeys)
            idx = np.clip(np.searchsorted(bkeys[order], words[:, 0]), 0, 49)
            hit = bkeys[order][idx] == words[:, 0]
            m = (f["c5"] < np.float32(0.2)) & hit
            extra = np.concatenate([bvals[order][idx[m]],
                                    np.zeros((m.sum(), 1), np.float32)], 1)
            return ref_select(words, m, extra=extra)
        return ref_select(words, f["c7"] < np.float32(0.1))

    # ---------------------------------------------- phase 1: served path
    server = FViewServer.start_in_thread(
        capacity_bytes=POOL_GIB << 30, max_payload=FRAME,
        io_timeout_s=900.0)
    answers = {}
    try:
        node = RemoteNodeHandle(server.host, server.port, timeout_s=900.0,
                                max_payload=FRAME)
        qp = fv.open_connection(node)
        t0 = time.perf_counter()
        ft = fv.alloc_table_mem(qp, FTable("lineitem8", cols, n_rows=n))
        fv.table_write(qp, ft, words)
        eft = fv.alloc_table_mem(qp, FTable("lineitem8_enc", cols,
                                            n_rows=n))
        fv.table_write(qp, eft, enc_words)
        dim = fv.alloc_table_mem(qp, FTable(
            "dim", (Column("k", "i32"), Column("v0"), Column("v1")),
            n_rows=50))
        fv.table_write(qp, dim, np.concatenate(
            [bkeys[:, None].astype(np.float32), bvals], 1))
        sft = fv.alloc_table_mem(qp, FTable(
            "logs", (Column("line", "str"),), n_rows=len(mat),
            str_width=mat.shape[1]))
        fv.table_write(qp, sft, mat.view(np.float32))
        log(f"served path: FViewServer on {server.host}:{server.port}, "
            f"pool {POOL_GIB} GiB, 4 tables loaded over the socket "
            f"({time.perf_counter() - t0:.1f}s)")

        for verb, queries in pipes.items():
            table = eft if verb == "pre_decrypt_select" else ft
            for qi, pipe in enumerate(queries):
                res, first, warm = timed(
                    lambda: fv.farview_request(qp, table, pipe).finalize())
                want = expect(verb, pipe, words)
                name = f"{verb}[{qi}]"
                if verb == "group_by":
                    n_ovf = len(res.groups["ovf_keys"])
                    got = fv.merge_group_partials(ft, pipe, [res]).groups
                    ok = same_groups(got, want) and n_ovf > 0
                    detail = f"{len(got)} groups, {n_ovf} overflow rows"
                else:
                    ok = same_rows(res.rows, want)
                    detail = f"{res.count} rows x {want.shape[1]} words"
                    answers.setdefault(verb, (pipe, res))
                check(name, ok, f"{detail} | first {first:.2f}s "
                      f"warm {warm:.3f}s")

        regex = (op.RegexMatch(PATTERN),)
        res, first, warm = timed(lambda: fv.farview_request(
            qp, sft, regex, strings=mat, lengths=lens).finalize())
        rx = re.compile(PATTERN.encode(), re.DOTALL)
        want = np.asarray([rx.search(bytes(r[:ln])) is not None
                           for r, ln in zip(mat, lens)])
        got = np.asarray(res.mask, bool)
        check("regex[0]", np.array_equal(got, want) and want.any(),
              f"{int(got.sum())}/{len(mat)} strings match | first "
              f"{first:.2f}s warm {warm:.3f}s")
        node.close()
    finally:
        server.stop_thread()
    del server
    gc.collect()

    # ----------------------------------- phase 2: 2-node FarCluster merge
    # cluster row ids ride the packing as f32, so a cluster table stays
    # under 2^24 rows: it holds all but the last row of the word table
    cw = words[: min(n, INT_EXACT_LIMIT - 1)]
    cl = FarCluster(2, capacity_bytes=POOL_GIB << 29)
    cqp = cl.open_connection()
    t0 = time.perf_counter()
    ct = cl.alloc_table_mem(cqp, FTable("lineitem8", cols, n_rows=len(cw)))
    cl.table_write(cqp, ct, cw)
    log(f"cluster: 2 FarCluster nodes on device 0, {POOL_GIB / 2:g} GiB "
        f"pools, {len(cw)} rows in range partitions "
        f"({time.perf_counter() - t0:.1f}s)")
    pipe, single = answers["select_project"]
    res, first, warm = timed(
        lambda: cl.farview_request(cqp, ct, pipe).finalize())
    one = np.asarray(single.rows, np.float32)[: res.count]
    check("cluster select_project",
          same_rows(res.rows, expect("select_project", pipe, cw))
          and same_rows(np.asarray(res.rows)[: res.count], one),
          f"{res.count} rows, byte-identical to numpy and to one node | "
          f"first {first:.2f}s warm {warm:.3f}s")
    pipe = pipes["group_by"][0]
    res, first, warm = timed(
        lambda: cl.farview_request(cqp, ct, pipe).finalize())
    check("cluster group_by",
          same_groups(res.groups, expect("group_by", pipe, cw)),
          f"{len(res.groups)} groups, exact | first {first:.2f}s "
          f"warm {warm:.3f}s")

    if check.failed:
        die(f"{len(check.failed)} check(s) failed: {check.failed}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
