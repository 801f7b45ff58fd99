"""One run of one cell: set-up, the measured window, the check, the line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

Set-up makes the table from the seed (its table kind,
`tables/<kind>.py`), stands up the served path (an in-process
`FViewServer` and a `RemoteNodeHandle` over localhost), writes the table
and the pool's other tables into the node's pool on the server's side
(loading is set-up, not traffic), and sends every instance of the mix
once, which compiles or loads each executable and warms the client's
side. The window then drives `farview_request(...).finalize()` and the
instance's verb's `answer` (`verbs/<verb>.py`: what the client holds once
the result is final) in a closed loop with one client, whole rounds of
the mix until `--seconds` have passed; it closes at the last answer. The
bytes each query brings the client are counted at its sockets
(`served.WireCount`), and the device memory in use is sampled while the
window runs. A sample of the answers, drawn from the seed (`KEEP` of each
instance's verb), is compared with the verb's plain reference once the
window has closed and the program is stopped.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, and last `checks`, each compared number with its limit. The
same numbers are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1, unless `--rehearse` is given: that runs the same path
at a tiny size on whatever JAX finds, for tests; its numbers are not
device numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from fvb import data as fdata
from fvb import spec as fspec
from fvb import traffic as ftraffic
from fvb import tracefold
from fvb.tracefold import WINDOW_SPAN

REHEARSAL_ROWS = 4096
REHEARSAL_POOL = 8 << 21            # 8 pages of 2 MiB
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MEMORY_PERIOD_S = 0.02              # device memory sampled this often


class NoChip(SystemExit):
    pass


@dataclass
class Query:
    inst: str
    t0: float
    t1: float
    resp_bytes: int                 # received at the client's sockets
    shipped: int
    read: int
    count: int                      # the verb's: survivors, overflow rows

    @property
    def latency(self) -> float:
        return self.t1 - self.t0


@dataclass
class Run:
    """What a metric reader reads."""
    spec: fspec.Spec
    insts: dict                     # name -> traffic.Instance
    n_rows: int
    width: int
    setup_s: float = 0.0
    queries: list = field(default_factory=list)
    failed: int = 0
    compiles_in_window: int = 0
    fold: object = None             # tracefold.Fold of a traced window
    device_kind: str = ""

    @property
    def span_s(self) -> float:
        if not self.queries:
            return 0.0
        return self.queries[-1].t1 - self.queries[0].t0

    @property
    def latencies(self) -> list:
        return [q.latency for q in self.queries]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any platform, for tests")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after the run)")
    return ap.parse_args(argv)


def _enable_cache(root: Path) -> None:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _devices(chips: int, rehearse: bool) -> dict:
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if not rehearse and (platform != "tpu" or len(devs) < chips):
        log(f"perfbench: needs {chips} TPU chip(s); JAX finds "
            f"{len(devs)} {platform} device(s)")
        raise NoChip(1)
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory(key: str) -> int:
    """`key` of the device memory stats, on the fullest chip (0 where the
    platform keeps none)."""
    import jax
    return max(int((d.memory_stats() or {}).get(key, 0))
               for d in jax.local_devices())


class MemoryWatch:
    """The most device memory in use while the window runs, sampled every
    MEMORY_PERIOD_S on a thread of its own: the chip's own peak counter
    never falls, so it would report set-up's transients (a pool write
    holds two copies of the pool)."""

    def __init__(self):
        self.peak = _memory("bytes_in_use")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self) -> None:
        while not self._stop.wait(MEMORY_PERIOD_S):
            self.peak = max(self.peak, _memory("bytes_in_use"))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, _memory("bytes_in_use"))


def _pool_off_cipher(pool, ft, stored: np.ndarray) -> int:
    """Words of the table's pool pages that differ from what set-up wrote."""
    import jax
    page = jax.jit(lambda buf, p: jax.lax.dynamic_index_in_dim(
        buf, p, keepdims=False))
    flat = stored.reshape(-1).view(np.uint32)
    bad = 0
    for i, p in enumerate(ft.pages):
        lo = i * pool.page_words
        n = min(pool.page_words, flat.size - lo)
        got = np.asarray(page(pool.buf, np.int32(p))).view(np.uint32)
        bad += int(np.count_nonzero(got[:n] != flat[lo: lo + n]))
    return bad


class Sample:
    """Answers kept for the check: per instance a reservoir of `k` drawn
    from the seed (every answer where k is None)."""

    def __init__(self, seed: int):
        self.rng = fdata.rng_of(seed, stream=2)
        self.kept: dict = {}
        self.seen: dict = {}

    def offer(self, name: str, answer, k: int | None) -> None:
        i = self.seen.get(name, 0)
        self.seen[name] = i + 1
        slot = self.kept.setdefault(name, [])
        if k is None or i < k:
            slot.append(answer)
        else:
            j = int(self.rng.integers(0, i + 1))
            if j < k:
                slot[j] = answer


def main(argv, t0: float | None = None,
         bench_dir: Path = fspec.BENCH_DIR) -> int:
    """Run the cell; the exit code. `bench_dir` is the benchmark's own
    directory; BENCHMARK.json and src/ are beside it."""
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    spec = fspec.load(args.workload, bench_dir)
    src = spec.root / "src"
    if not (src / "repro").is_dir():
        log(f"perfbench: no program at {src / 'repro'}")
        return 2
    sys.path.insert(0, str(src))
    try:
        return _run(args, spec, t0)
    except NoChip as e:
        return int(e.code)


def _run(args, spec: fspec.Spec, t0: float) -> int:
    import jax
    from jax.profiler import TraceAnnotation

    from fvb import served
    from repro.core import client as fv
    from repro.core.table import Column, FTable

    if not args.rehearse:
        _enable_cache(spec.root)
    device = _devices(int(spec.workload["chips"]), args.rehearse)
    cfg = spec.config
    n_rows = REHEARSAL_ROWS if args.rehearse else int(cfg["rows"])
    pool_bytes = REHEARSAL_POOL if args.rehearse else int(cfg["pool_bytes"])
    insts = ftraffic.instances(spec)
    run = Run(spec=spec, insts={i.name: i for i in insts},
              n_rows=n_rows, width=len(cfg["columns"]),
              device_kind=device["kind"])

    compiles = [0]

    def on_event(event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    # ------------------------------------------------------------- set-up
    t_data = time.perf_counter()
    table = spec.table_kind.make_table(cfg, args.seed, n_rows)
    at_rest = cfg.get("encrypted_at_rest")
    stored = fdata.encrypt(table.words, at_rest) if at_rest else table.words
    cols = tuple(Column(c["name"], c["dtype"]) for c in cfg["columns"])
    server, handle = served.start(cfg, pool_bytes)
    sample = Sample(args.seed)
    checks: dict = {}
    window_s = 0.0
    tdir = None
    wire = served.WireCount(server.port)
    short = [0]
    try:
        t_pool = time.perf_counter()
        qp = fv.open_connection(handle)
        ft = fv.alloc_table_mem(qp, FTable(cfg["name"], cols, n_rows=n_rows))
        server.node.pool.write_table(ft, stored)
        # a write holds two copies of the pool until it has run: let each
        # finish before the next table is made
        jax.block_until_ready(server.node.pool.buf)
        for t in range(1, int(cfg["tables"])):
            other = fv.alloc_table_mem(
                qp, FTable(f"{cfg['name']}.{t}", cols, n_rows=n_rows))
            server.node.pool.write_table(
                other, spec.table_kind.other_table(cfg, args.seed, t,
                                                   n_rows))
            jax.block_until_ready(server.node.pool.buf)
        log(f"table and server ready in {t_pool - t_data:.3f}s; pool written in "
            f"{time.perf_counter() - t_pool:.3f}s: {cfg['tables']} tables, "
            f"{server.node.pool.free_pages} pages free")

        def query(inst) -> tuple:
            conns, b0 = wire.read()
            q0 = time.perf_counter()
            res = fv.farview_request(qp, ft, inst.pipeline)
            with TraceAnnotation("fv.finalize"):
                res = res.finalize()
            answer, count = inst.verb.answer(res, ft, inst.pipeline)
            q1 = time.perf_counter()
            conns1, b1 = wire.read()
            if conns1 != conns:
                raise RuntimeError("the client's connections to the server "
                                   "changed during a query")
            q = Query(inst.name, q0, q1, b1 - b0, int(res.shipped_bytes),
                      int(res.read_bytes), count)
            if q.resp_bytes < inst.verb.answer_bytes(
                    inst.spec, int(cfg["word_bytes"]), run.width, q.count):
                short[0] += 1
            return answer, q

        for inst in insts:                      # warm every shape
            _, q = query(inst)
            log(f"warm {q.inst}: {q.latency:.3f}s {q.resp_bytes} B")
        run.setup_s = time.perf_counter() - t0
        log(f"setup {run.setup_s:.3f}s")

        if args.trace:
            tdir = args.trace_dir or tempfile.mkdtemp(prefix="fvb-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        c0 = compiles[0]
        log(f"device memory: {_memory('bytes_in_use')} B in use, "
            f"{_memory('peak_bytes_in_use')} B peak in set-up")
        try:
            with TraceAnnotation(WINDOW_SPAN), MemoryWatch() as memory:
                w0 = time.perf_counter()
                for round_ in ftraffic.rounds(insts, args.seed):
                    if time.perf_counter() - w0 >= args.seconds:
                        break
                    for inst in round_:
                        try:
                            answer, q = query(inst)
                        except Exception as e:      # noqa: BLE001 - counted
                            run.failed += 1
                            log(f"query {inst.name} failed: {e!r}")
                            raise
                        run.queries.append(q)
                        sample.offer(q.inst, answer, inst.verb.KEEP)
                        log(f"q {q.inst}: {q.latency:.4f}s "
                            f"{q.resp_bytes} B")
                window_s = time.perf_counter() - w0
        finally:
            run.compiles_in_window = compiles[0] - c0
            if args.trace:
                jax.profiler.stop_trace()
        device["memory_peak_bytes"] = memory.peak
        if at_rest:
            checks["pool_off_cipher"] = _pool_off_cipher(
                server.node.pool, ft, stored)
    except Exception as e:                  # noqa: BLE001 - reported below
        log(f"run failed: {e!r}")
        run.failed = max(run.failed, 1)
    finally:
        device.setdefault("memory_peak_bytes", _memory("bytes_in_use"))
        handle.close()
        server.stop_thread()
        del server, handle
        jax.monitoring.unregister_event_duration_listener(on_event)

    # -------------------------------------------------- after the window
    for name, answers in sample.kept.items():
        verb = run.insts[name].verb
        want = verb.expect(table.words, table.index, run.insts[name].spec)
        for ans in answers:
            for k, v in verb.compare(ans, want).items():
                checks[k] = checks.get(k, 0) + v
    del sample
    checks["resp_below_answer"] = short[0]
    limits = dict(spec.limits, pool_off_cipher=0, resp_below_answer=0)
    answered = {q.inst for q in run.queries}
    correct = (run.failed == 0 and answered == set(run.insts)
               and all(v <= limits[k] for k, v in checks.items()))

    breakdown = None
    if tdir is not None:
        try:
            run.fold = tracefold.fold(tracefold.load(Path(tdir)))
        finally:
            if args.trace_dir is None:
                shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = run.fold.busy_s
        device["window_s"] = run.fold.window_s
        breakdown = {"device_ops": [list(x) for x in run.fold.top_ops],
                     "idle_gaps": [list(x) for x in run.fold.gaps]}
    log(f"window {window_s:.3f}s, {len(run.queries)} queries, "
        f"{run.compiles_in_window} compiles")

    wanted = spec.per_layer if args.trace else spec.end_to_end
    metrics = {}
    for m in wanted:
        value = fspec.reader(spec.bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(run.queries)
           + run.failed, "failed": run.failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": limits[k]}
                     for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} {v} limit {limits[k]}")
    print(json.dumps(out), flush=True)
    return 0

