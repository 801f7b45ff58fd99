"""Network tier under fan-in: latency vs connection count + shed behavior.

One `FViewServer` (thread-hosted, same binary the CI server-smoke lane
runs as a subprocess on the CPU; on a TPU it must stay a thread of the
process holding the chip) and an asyncio load generator speaking raw
`net/wire.py` frames — no client-library batching, so what is measured
is the server's own multiplexing: admission, the 2 ms coalescing
window, ONE worker-thread flush per drain round, completion-order
replies.

Two phases:

  submit_cC    C concurrent connections over one shared table, each
               issuing sequential SUBMITs (selection pipeline) and
               awaiting its RESULT. Reported us_per_call is the p50
               request latency, plus p99_us — the fan-in curve
               p99(C)/p50(1) is the CI guard
               (`check_regression --max-p99-ratio`): connection count
               must buy throughput, not unbounded tail latency. Every
               request in this phase must complete (depth 4096 admits
               the whole sweep); a shed here fails the bench.

  overload_cC  a deliberately tiny admission bound (depth 64), every
               connection bursting SUBMITs without awaiting. The
               contract under load: shed requests get an immediate
               typed OVERLOADED frame (never a hang, never a
               half-run), accepted requests ALL complete, and
               shed + completed == sent exactly.

Full mode sweeps 1/64/256/1024 connections (the 1k+ acceptance row);
quick mode keeps 1 and 256 for the regression guard.

Standalone:  python -m benchmarks.bench_network --json BENCH.json
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmarks import common
from repro.core import operators as op
from repro.core.table import Column, FTable
from repro.net import wire
from repro.net.server import FViewServer

N = 4096
COLS = tuple(Column(f"c{i}", "i32" if i == 0 else "f32") for i in range(6))
# ~5% selectivity: responses stay small, the wire cost is the protocol,
# not a bulk row ship
PIPE = (op.Select((op.Predicate("c1", "<", -45.0),)),)
CONNECT_PARALLELISM = 128


def _make_words(rng) -> np.ndarray:
    d = {"c0": rng.integers(0, 13, N).astype(np.int32)}
    for i in range(1, 6):
        d[f"c{i}"] = rng.integers(-50, 50, N).astype(np.float32)
    return FTable("t", COLS, n_rows=N).encode(d)


async def _read_frame(reader):
    hdr = await reader.readexactly(wire.HEADER_SIZE)
    ftype, req_id, length = wire.parse_header(hdr)
    body = await reader.readexactly(length) if length else b""
    trailer = await reader.readexactly(wire.TRAILER_SIZE)
    wire.check_crc(hdr, body, trailer)
    return ftype, req_id, (wire.decode_value(body) if length else None)


async def _open_conn(host, port, vqp_out):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(wire.encode_frame(wire.HELLO, 0,
                                   {"version": wire.VERSION}))
    await writer.drain()
    ftype, _, _ = await _read_frame(reader)
    assert ftype == wire.HELLO_OK
    writer.write(wire.encode_frame(wire.OPEN_QP, 1))
    await writer.drain()
    _, _, payload = await _read_frame(reader)
    vqp_out.append(payload["qp"])
    return reader, writer


def _submit_payload(vqp: int, table_id: int) -> dict:
    return {"qp": vqp, "table_id": table_id, "pipeline": PIPE,
            "lengths": None, "strings": None, "row_ids": None}


async def _latency_client(host, port, table_id, n_reqs, latencies):
    vqp = []
    reader, writer = await _open_conn(host, port, vqp)
    payload = _submit_payload(vqp[0], table_id)
    try:
        for i in range(n_reqs):
            t0 = time.perf_counter()
            writer.write(wire.encode_frame(wire.SUBMIT, 2 + i, payload))
            await writer.drain()
            ftype, _, _ = await _read_frame(reader)
            latencies.append(time.perf_counter() - t0)
            if ftype != wire.RESULT:
                raise RuntimeError(
                    f"latency sweep expected RESULT, got "
                    f"{wire.FRAME_NAMES.get(ftype, ftype)}")
    finally:
        writer.close()


async def _burst_client(host, port, table_id, burst, counts):
    vqp = []
    reader, writer = await _open_conn(host, port, vqp)
    payload = _submit_payload(vqp[0], table_id)
    try:
        for i in range(burst):
            writer.write(wire.encode_frame(wire.SUBMIT, 2 + i, payload))
        await writer.drain()
        for _ in range(burst):
            ftype, _, _ = await _read_frame(reader)
            if ftype == wire.RESULT:
                counts["completed"] += 1
            elif ftype == wire.OVERLOADED:
                counts["shed"] += 1
            else:
                raise RuntimeError(
                    f"burst expected RESULT/OVERLOADED, got "
                    f"{wire.FRAME_NAMES.get(ftype, ftype)}")
    finally:
        writer.close()


async def _fan_out(factory, n_conns):
    """Run one client task per connection, opening conns in bounded
    parallel waves so 1k+ connects don't SYN-storm the accept loop."""
    sem = asyncio.Semaphore(CONNECT_PARALLELISM)

    async def _one(i):
        async with sem:
            return await factory(i)

    results = await asyncio.gather(*(_one(i) for i in range(n_conns)))
    return results


def _percentile(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def _alloc_shared_table(server, words) -> int:
    """Alloc + write the one table every connection hammers (in-process:
    the bench owns the server, so it uses the node directly)."""
    ft = FTable("t", COLS, n_rows=N)
    server.node.pool.alloc_table(ft)
    server.node.pool.write_table(ft, words)
    server._tables[ft.table_id] = ft
    return ft.table_id


def run() -> None:
    q = common.quick()
    conn_counts = (1, 256) if q else (1, 64, 256, 1024)
    rng = np.random.default_rng(0)
    words = _make_words(rng)

    # ---- phase 1: latency vs fan-in (no shedding permitted) ----------
    server = FViewServer.start_in_thread(max_queue_depth=4096,
                                         max_conns=8192)
    table_id = _alloc_shared_table(server, words)
    host, port = server.host, server.port
    try:
        for n_conns in conn_counts:
            n_reqs = (max(4, 512 // n_conns) if q
                      else max(8, 2048 // n_conns))
            # warmup at THIS fan-in: the first rounds pay the jit
            # compile per stack-size bucket; keep them out of p50/p99
            asyncio.run(_fan_out(
                lambda i: _latency_client(host, port, table_id, 2, []),
                n_conns))
            latencies: list[float] = []
            t0 = time.perf_counter()
            asyncio.run(_fan_out(
                lambda i: _latency_client(host, port, table_id, n_reqs,
                                          latencies), n_conns))
            wall = time.perf_counter() - t0
            total = n_conns * n_reqs
            common.row("network", f"submit_c{n_conns}",
                       _percentile(latencies, 0.50) * 1e6,
                       connections=n_conns, reqs=total,
                       p99_us=round(_percentile(latencies, 0.99) * 1e6, 1),
                       reqs_per_s=round(total / wall, 1), shed=0)
    finally:
        server.stop_thread()

    # ---- phase 2: overload -> typed shed, accepted all complete ------
    over = FViewServer.start_in_thread(max_queue_depth=64, max_conns=8192)
    table_id = _alloc_shared_table(over, words)
    host, port = over.host, over.port
    try:
        n_conns = conn_counts[-1]
        burst = 4 if q else 8
        counts = {"completed": 0, "shed": 0}
        t0 = time.perf_counter()
        asyncio.run(_fan_out(
            lambda i: _burst_client(host, port, table_id, burst, counts),
            n_conns))
        wall = time.perf_counter() - t0
        sent = n_conns * burst
        assert counts["completed"] + counts["shed"] == sent, counts
        assert counts["shed"] > 0, "overload phase never hit the bound"
        assert counts["completed"] > 0, "admission starved everyone"
        common.row("network", f"overload_c{n_conns}", wall / sent * 1e6,
                   connections=n_conns, reqs=sent,
                   completed=counts["completed"], shed=counts["shed"],
                   shed_frac=round(counts["shed"] / sent, 3))
    finally:
        over.stop_thread()


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.quick:
        common.QUICK = True
    run()
    common.print_csv()
    if args.json:
        common.write_json(args.json)


if __name__ == "__main__":
    main()
