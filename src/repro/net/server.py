"""`FViewServer`: one smart memory node behind a real TCP socket.

The asyncio front-end multiplexes thousands of client connections into
the ONE in-process `FViewNode` scheduler that PR 2 built:

  * every connection's `OPEN_QP` gets a *virtual* QPair, mapped
    round-robin onto a small fixed set of real QPairs (one per dynamic
    region, the paper's 6-ish) opened at server start — so connection
    count scales far past region count while the scheduler still sees
    its normal per-region fair-share arbitration;
  * `SUBMIT` frames are ADMITTED (or shed — below) into per-tenant
    queues; a background drain task collects a short batching window,
    interleaves tenants round-robin, and pushes the whole batch through
    `node.submit` + ONE `node.flush()` on a single worker thread. All
    same-(signature, layout, bucket) requests from different
    connections therefore land in the same scheduling round and
    coalesce into one stacked executable — PR 2's cross-client
    batching, preserved byte-for-byte across the socket;
  * results are finalized on the worker thread and shipped back as
    typed `RESULT` / `ERROR` frames correlated by request id, in
    completion order.

Backpressure is admission control, not TCP: a bounded global queue
depth plus a per-tenant fair share (`depth // active_tenants`). A
request past either bound is answered immediately with a typed
`OVERLOADED` frame (`OverloadedError` client-side) instead of queueing
toward a pool OOM or an unbounded p99 — the shed is explicit, cheap,
and never touches the scheduler. Accepted requests always complete.

Everything that can block — pool verbs, `node.flush()`, jit compiles,
`finalize()` — runs on a single `ThreadPoolExecutor` worker, keeping
the event loop free to accept, shed and answer (farlint FL006 enforces
this: no blocking calls inside `async def` under net/).

Run standalone:  python -m repro.net.server --port 0 --log server.log
(prints ``LISTENING <port>`` on stdout once bound — the CI server-smoke
lane and the subprocess test harness both key on that line).
"""
from __future__ import annotations

import argparse
import asyncio
import itertools
import sys
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from repro import compile_cache
from repro.core import client as fv
from repro.net import wire


class ServerLifecycleError(fv.FarviewError):
    """A server start/stop step timed out or failed: the thread never
    came up, boot raised, or shutdown leaked the thread. Typed and LOUD —
    the old behavior (fall through a `ready.wait` / `thread.join`
    timeout and keep going) turned a wedged server into a mystery
    failure three tests later."""


def _result_payload(res) -> dict:
    """Flatten a FINALIZED PipelineResult into wire values. The client
    rebuilds an already-finalized result from these — `offload._merge`
    reads only kind/count/rows/sel_ids/mask/groups/shipped/read, so the
    rebuilt partial merges byte-identically to an in-process one."""
    out = {"kind": res.kind, "count": res._count,
           "shipped": int(res._shipped or 0),
           "read": int(res.read_bytes or 0)}
    if res.rows is not None:
        rows = np.asarray(res.rows)
        if not res._meta.get("sealed") and res._count is not None:
            # survivors only; the client restores the zero tail. A sealed
            # (post-encrypted) response ships whole: its tail is keystream
            out["n_rows"] = int(rows.shape[0])
            rows = rows[: res._count]
        out["rows"] = rows
    if res._ids is not None:
        out["sel_ids"] = np.asarray(res._ids)
    if res.mask is not None:
        out["mask"] = np.asarray(res.mask)
    if res._groups is not None:
        out["groups"] = {
            k: (np.asarray(v) if isinstance(v, (np.ndarray, list))
                or hasattr(v, "__array__") else v)
            for k, v in res._groups.items()}
    return out


@dataclass
class _Submit:
    """One admitted SUBMIT, from frame to RESULT/ERROR reply."""
    conn: "_Conn"
    req_id: int
    vqp: int
    real_qp: object
    ft: object
    pipeline: tuple
    lengths: object = None
    strings: object = None
    row_ids: object = None
    pend: object = None             # PendingRequest once submitted
    payload: dict | None = None     # RESULT payload once finalized
    error: Exception | None = None
    done: asyncio.Future = None     # resolved after the reply frame
    deadline: float | None = None   # time.monotonic() expiry from the
    #                                 frame's deadline_ms budget; checked
    #                                 again right before dispatch


class _Conn:
    """Per-connection state: virtual QPairs, admission queue, in-flight
    request ledger (for FLUSH barriers and disconnect cleanup)."""

    def __init__(self, conn_id: int, reader, writer):
        self.conn_id = conn_id
        self.reader = reader
        self.writer = writer
        self.wlock = asyncio.Lock()     # one frame at a time per socket
        self.vqps: dict[int, object] = {}   # virtual qp -> real QPair
        self.queue: deque[_Submit] = deque()    # admitted, not yet drained
        self.entries: dict[int, _Submit] = {}   # req_id -> in-flight
        self.closed = False


class FViewServer:
    """Asyncio server wrapping one `FViewNode` (see module docstring)."""

    def __init__(self, node: "fv.FViewNode | None" = None, *,
                 host: str = "127.0.0.1", port: int = 0,
                 capacity_bytes: int = 64 * 2**20, n_regions: int = 6,
                 interpret: bool | None = None, node_id: int = 0,
                 max_queue_depth: int = 1024, max_conns: int = 4096,
                 flush_interval_s: float = 0.002,
                 max_payload: int = wire.MAX_PAYLOAD,
                 io_timeout_s: float = 60.0,
                 idle_timeout_s: float = 3600.0,
                 log_path: str | None = None):
        self.node = node if node is not None else fv.FViewNode(
            capacity_bytes, n_regions=n_regions, interpret=interpret,
            node_id=node_id)
        self.host = host
        self.port = port                # real port known after start()
        self.max_queue_depth = int(max_queue_depth)
        self.max_conns = int(max_conns)
        self.flush_interval_s = float(flush_interval_s)
        self.max_payload = int(max_payload)
        # every await on the socket is BOUNDED (farlint FL007): a peer
        # that stalls mid-frame is reaped after io_timeout_s, an idle
        # connection (between requests) after idle_timeout_s
        self.io_timeout_s = float(io_timeout_s)
        self.idle_timeout_s = float(idle_timeout_s)
        self._log_file = open(log_path, "a") if log_path else None
        self._conn_ids = itertools.count()
        self._vqp_ids = itertools.count()
        self._conns: set[_Conn] = set()
        self._real_qps: list = []
        self._inflight_total = 0
        self._shed_total = 0
        self._deadline_shed_total = 0
        self._closing = False
        self._server: asyncio.AbstractServer | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._wake: asyncio.Event | None = None
        self._flush_urgent = False
        self._drain_task: asyncio.Task | None = None
        self._stopped: asyncio.Event | None = None
        # ONE worker: every node/pool/jit touch is serialized here, so
        # the FViewNode needs no locking and the loop never blocks
        self._exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"fview-node{self.node.node_id}")
        self._tables: dict[int, object] = {}    # table_id -> server FTable

    # -------------------------------------------------------------- logging
    def log(self, msg: str) -> None:
        line = f"[{time.strftime('%H:%M:%S')}] node{self.node.node_id} {msg}"
        out = self._log_file or sys.stderr
        print(line, file=out, flush=True)

    # ------------------------------------------------------------ lifecycle
    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._wake = asyncio.Event()
        self._stopped = asyncio.Event()
        for _ in range(len(self.node.regions)):
            self._real_qps.append(self.node.open_connection())
        self._server = await asyncio.start_server(
            self._serve_conn, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._drain_task = asyncio.ensure_future(self._drain_loop())
        self.log(f"listening on {self.host}:{self.port} "
                 f"(regions={len(self._real_qps)}, "
                 f"depth={self.max_queue_depth})")

    async def run_forever(self) -> None:
        await self.start()
        print(f"LISTENING {self.port}", flush=True)
        await self._stopped.wait()

    def shutdown(self, *, abort: bool = False) -> None:
        """Thread-safe stop. `abort=True` hard-drops every live socket
        (transport.abort — a RST, not a FIN), which is how the failover
        tests simulate a dying server across a REAL connection drop."""
        if self._loop is None:
            return
        try:
            self._loop.call_soon_threadsafe(self._do_shutdown, abort)
        except RuntimeError:
            pass                        # loop already closed

    def _do_shutdown(self, abort: bool) -> None:
        if self._closing:
            return
        self._closing = True
        self.log(f"shutdown (abort={abort})")
        if self._server is not None:
            self._server.close()
        for conn in list(self._conns):
            conn.closed = True
            if abort:
                conn.writer.transport.abort()
            else:
                conn.writer.close()
        if self._drain_task is not None:
            self._drain_task.cancel()
        self._exec.shutdown(wait=False)
        self._stopped.set()

    # Thread-hosted mode: tests and benches run servers inside the test
    # process; CI's server-smoke lane runs them as real subprocesses.
    @classmethod
    def start_in_thread(cls, *, start_timeout_s: float = 60.0,
                        **kwargs) -> "FViewServer":
        srv = cls(**kwargs)
        ready = threading.Event()
        boot_err: list[BaseException] = []

        def _run() -> None:
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)

            async def _main() -> None:
                try:
                    await srv.start()
                except BaseException as e:  # noqa: BLE001 - reported below
                    boot_err.append(e)
                    ready.set()
                    return
                ready.set()
                await srv._stopped.wait()
                # reap the per-connection tasks the shutdown just woke
                # (the FL007 wait_for wrappers add a loop iteration to
                # their wakeup chain), so the loop closes with nothing
                # pending — asyncio.run does this for the __main__ path
                pending = [t for t in asyncio.all_tasks()
                           if t is not asyncio.current_task()]
                for t in pending:
                    t.cancel()
                await asyncio.gather(*pending, return_exceptions=True)

            try:
                loop.run_until_complete(_main())
            finally:
                loop.close()

        srv._thread = threading.Thread(target=_run, daemon=True)
        srv._thread.start()
        # both failure modes are TYPED (ServerLifecycleError), never a
        # silent fall-through into verbs against a server that isn't up
        if not ready.wait(timeout=start_timeout_s):
            raise ServerLifecycleError(
                f"FViewServer did not come up within {start_timeout_s:.0f}s "
                "(event loop thread never signalled ready)")
        if boot_err:
            raise ServerLifecycleError(
                f"FViewServer failed to start: {boot_err[0]}") from boot_err[0]
        return srv

    def stop_thread(self, *, abort: bool = False,
                    join_timeout_s: float = 30.0) -> None:
        self.shutdown(abort=abort)
        thread = getattr(self, "_thread", None)
        leaked = False
        if thread is not None:
            thread.join(timeout=join_timeout_s)
            leaked = thread.is_alive()
            if leaked:
                self.log(f"stop_thread: server thread still alive "
                         f"{join_timeout_s:.0f}s after shutdown (leaked)")
        if self._log_file is not None:
            self._log_file.close()
            self._log_file = None
        if leaked:
            raise ServerLifecycleError(
                f"server thread (node {self.node.node_id}, port "
                f"{self.port}) did not exit within {join_timeout_s:.0f}s "
                "of shutdown — thread leaked")

    # ------------------------------------------------------------ admission
    def _active_tenants(self) -> int:
        return sum(1 for c in self._conns if c.queue or c.entries)

    def _admit(self, conn: _Conn) -> str | None:
        """None to admit, else the shed reason (typed OVERLOADED)."""
        if self._inflight_total >= self.max_queue_depth:
            return (f"queue depth {self._inflight_total} at the "
                    f"{self.max_queue_depth} bound")
        share = max(1, self.max_queue_depth
                    // max(1, self._active_tenants()))
        mine = len(conn.queue) + len(conn.entries)
        if mine >= share:
            return (f"tenant at fair share ({mine} in flight, "
                    f"share {share})")
        return None

    # ------------------------------------------------------------- the drain
    async def _drain_loop(self) -> None:
        while not self._closing:
            await self._wake.wait()
            self._wake.clear()
            if self.flush_interval_s and not self._flush_urgent:
                # batching window: let concurrent submits pile into ONE
                # scheduler round (cross-client coalescing)
                await asyncio.sleep(self.flush_interval_s)
            self._flush_urgent = False
            batch = self._take_batch()
            if not batch:
                continue
            try:
                await self._loop.run_in_executor(
                    self._exec, self._run_batch, batch)
            except Exception as e:      # noqa: BLE001 - worker died
                for ent in batch:
                    ent.error = ent.error or e
            for ent in batch:
                await self._finish_entry(ent)

    def _take_batch(self) -> list:
        """Round-robin interleave of every tenant's admitted queue, so
        one chatty connection cannot monopolize a scheduler round."""
        batch: list[_Submit] = []
        ready = [c for c in self._conns if c.queue]
        while ready:
            still = []
            for conn in ready:
                batch.append(conn.queue.popleft())
                if conn.queue:
                    still.append(conn)
            ready = still
        return batch

    def _run_batch(self, batch: list) -> None:
        """Worker-thread half: submit everything, ONE flush, finalize."""
        for ent in batch:
            if ent.error is not None:
                continue
            if (ent.deadline is not None
                    and time.monotonic() >= ent.deadline):
                # budget spent while queued behind the batching window:
                # shed BEFORE dispatch — an expired request never
                # half-runs (and never costs a scheduler round)
                self._deadline_shed_total += 1
                ent.error = fv.DeadlineExceededError(
                    self.node.node_id, op="dispatch",
                    detail="budget spent in the server queue")
                continue
            try:
                ent.pend = self.node.submit(
                    ent.real_qp, ent.ft, ent.pipeline, lengths=ent.lengths,
                    strings=ent.strings, row_ids=ent.row_ids,
                    deadline_s=None if ent.deadline is None
                    else ent.deadline - time.monotonic())
            except Exception as e:      # noqa: BLE001 - typed reply below
                ent.error = e
        try:
            self.node.flush()
        except Exception:               # noqa: BLE001
            pass        # per-request errors live on their PendingRequests
        for ent in batch:
            if ent.error is not None or ent.pend is None:
                continue
            if ent.pend.error is not None:
                ent.error = ent.pend.error
            elif ent.pend.result is None:
                ent.error = fv.FarviewError("request was not dispatched")
            else:
                try:
                    ent.payload = _result_payload(ent.pend.result.finalize())
                except Exception as e:  # noqa: BLE001
                    ent.error = e

    async def _finish_entry(self, ent: _Submit) -> None:
        conn = ent.conn
        conn.entries.pop(ent.req_id, None)
        self._inflight_total -= 1
        if not conn.closed:
            try:
                if ent.error is not None:
                    await self._send(conn, wire.ERROR, ent.req_id,
                                     wire.encode_error(
                                         ent.error,
                                         node_id=self.node.node_id))
                else:
                    await self._send(conn, wire.RESULT, ent.req_id,
                                     ent.payload)
            except (ConnectionError, RuntimeError):
                conn.closed = True
        if ent.done is not None and not ent.done.done():
            ent.done.set_result(None)

    # ----------------------------------------------------------- connection
    async def _send(self, conn: _Conn, ftype: int, req_id: int,
                    obj=None) -> None:
        data = wire.encode_frame(ftype, req_id, obj,
                                 max_payload=self.max_payload)
        async with conn.wlock:
            conn.writer.write(data)
            try:
                # bounded (FL007): a peer that stops reading must not pin
                # this coroutine (and the conn's write lock) forever
                await asyncio.wait_for(conn.writer.drain(),
                                       self.io_timeout_s)
            except asyncio.TimeoutError:
                # to every caller a stalled peer IS a dead transport
                raise ConnectionError(
                    f"conn{conn.conn_id}: send stalled past "
                    f"{self.io_timeout_s:.0f}s io timeout") from None

    async def _serve_conn(self, reader, writer) -> None:
        conn = _Conn(next(self._conn_ids), reader, writer)
        if self._closing or len(self._conns) >= self.max_conns:
            try:
                await self._send(conn, wire.OVERLOADED, 0,
                                 {"node_id": self.node.node_id,
                                  "detail": f"at {self.max_conns} "
                                            "connections"})
            except (ConnectionError, RuntimeError):
                pass
            writer.close()
            return
        self._conns.add(conn)
        try:
            while not self._closing:
                try:
                    # idle bound between requests, io bound mid-frame:
                    # every read is inside wait_for (farlint FL007)
                    hdr = await asyncio.wait_for(
                        reader.readexactly(wire.HEADER_SIZE),
                        self.idle_timeout_s)
                    ftype, req_id, length = wire.parse_header(
                        hdr, max_payload=self.max_payload)
                    body = (await asyncio.wait_for(
                        reader.readexactly(length), self.io_timeout_s)
                        if length else b"")
                    trailer = await asyncio.wait_for(
                        reader.readexactly(wire.TRAILER_SIZE),
                        self.io_timeout_s)
                    wire.check_crc(hdr, body, trailer)
                    payload = wire.decode_value(body) if length else None
                except (asyncio.IncompleteReadError, ConnectionError):
                    break               # peer went away mid-frame / EOF
                except asyncio.TimeoutError:
                    self.log(f"conn{conn.conn_id} reaped: socket idle/"
                             "stalled past its timeout")
                    break
                except wire.ProtocolError as e:
                    # poisoned stream: answer typed, then drop THIS conn
                    self.log(f"conn{conn.conn_id} protocol error: {e}")
                    try:
                        await self._send(conn, wire.ERROR, 0,
                                         wire.encode_error(
                                             e, node_id=self.node.node_id))
                    except (ConnectionError, RuntimeError):
                        pass
                    break
                try:
                    await self._handle(conn, ftype, req_id, payload)
                # FarviewError IS a RuntimeError: match it first so typed
                # app errors reply instead of tripping the transport guard
                except fv.FarviewError as e:
                    try:
                        await self._send(conn, wire.ERROR, req_id,
                                         wire.encode_error(
                                             e, node_id=self.node.node_id))
                    except (ConnectionError, RuntimeError):
                        break
                except (ConnectionError, RuntimeError):
                    break               # transport died under the handler
                except Exception as e:  # noqa: BLE001 - reply, don't die
                    try:
                        await self._send(conn, wire.ERROR, req_id,
                                         wire.encode_error(
                                             e, node_id=self.node.node_id))
                    except (ConnectionError, RuntimeError):
                        break
        finally:
            self._drop_conn(conn)

    def _drop_conn(self, conn: _Conn) -> None:
        conn.closed = True
        self._conns.discard(conn)
        # admitted-but-undrained entries: nobody is listening anymore
        while conn.queue:
            ent = conn.queue.popleft()
            conn.entries.pop(ent.req_id, None)
            self._inflight_total -= 1
            if ent.done is not None and not ent.done.done():
                ent.done.set_result(None)
        try:
            conn.writer.close()
        except RuntimeError:
            pass

    # -------------------------------------------------------------- handlers
    async def _handle(self, conn: _Conn, ftype: int, req_id: int,
                      payload) -> None:
        if ftype == wire.HELLO:
            want = (payload or {}).get("version")
            if want != wire.VERSION:
                raise wire.ProtocolError(
                    f"client speaks wire version {want}, server "
                    f"{wire.VERSION}")
            await self._send(conn, wire.HELLO_OK, req_id,
                             {"version": wire.VERSION,
                              "node_id": self.node.node_id,
                              "n_regions": len(self._real_qps)})
        elif ftype == wire.OPEN_QP:
            vqp = next(self._vqp_ids)
            conn.vqps[vqp] = self._real_qps[vqp % len(self._real_qps)]
            await self._send(conn, wire.OK, req_id, {"qp": vqp})
        elif ftype == wire.CLOSE_QP:
            vqp = payload["qp"]
            conn.vqps.pop(vqp, None)
            still = deque()
            for ent in conn.queue:      # cancel the vqp's queued verbs
                if ent.vqp == vqp:
                    ent.error = fv.FarviewError(
                        f"connection qp{vqp} closed with request pending")
                    await self._finish_entry(ent)
                else:
                    still.append(ent)
            conn.queue = still
            await self._send(conn, wire.OK, req_id, {})
        elif ftype == wire.SUBMIT:
            await self._handle_submit(conn, req_id, payload)
        elif ftype == wire.FLUSH:
            # barrier over THIS connection's in-flight verbs: later
            # submits ride later drains and do not extend the wait
            waiters = [ent.done for ent in conn.entries.values()]
            self._flush_urgent = True
            self._wake.set()
            if waiters:
                await asyncio.wait(waiters)
            await self._send(conn, wire.OK, req_id, {})
        elif ftype == wire.STATS:
            stats = await self._loop.run_in_executor(
                self._exec, self._stats_payload)
            await self._send(conn, wire.OK, req_id, stats)
        elif ftype in (wire.ALLOC, wire.FREE, wire.REGISTER,
                       wire.UNREGISTER, wire.WRITE, wire.READ,
                       wire.READ_ROWS):
            reply = await self._loop.run_in_executor(
                self._exec, self._pool_verb, ftype, payload)
            await self._send(conn, wire.OK, req_id, reply)
        else:
            raise wire.ProtocolError(
                f"frame {wire.FRAME_NAMES.get(ftype, ftype)!r} is not a "
                "client request")

    async def _handle_submit(self, conn: _Conn, req_id: int,
                             payload) -> None:
        reason = self._admit(conn)
        if reason is not None:
            self._shed_total += 1
            await self._send(conn, wire.OVERLOADED, req_id,
                             {"node_id": self.node.node_id,
                              "detail": reason})
            return
        # deadline budget (PR 9): the frame carries the REMAINING budget
        # in ms; re-anchor it on this host's monotonic clock. A request
        # that arrives already expired is shed right here — typed
        # DEADLINE_EXCEEDED, zero scheduler work
        deadline_ms = payload.get("deadline_ms")
        deadline = None
        if deadline_ms is not None:
            if float(deadline_ms) <= 0:
                self._deadline_shed_total += 1
                await self._send(
                    conn, wire.ERROR, req_id,
                    wire.encode_error(fv.DeadlineExceededError(
                        self.node.node_id, op="admission",
                        detail="budget already spent on arrival"),
                        node_id=self.node.node_id))
                return
            deadline = time.monotonic() + float(deadline_ms) / 1e3
        vqp = payload["qp"]
        real_qp = conn.vqps.get(vqp)
        if real_qp is None:
            raise fv.FarviewError(f"connection qp{vqp} is closed")
        ft = self._tables.get(payload["table_id"])
        if ft is None:
            raise fv.FarviewError(
                f"unknown table_id {payload['table_id']} (not allocated "
                "on this node)")
        row_ids = payload.get("row_ids")
        ent = _Submit(
            conn=conn, req_id=req_id, vqp=vqp, real_qp=real_qp, ft=ft,
            pipeline=tuple(payload["pipeline"]),
            lengths=payload.get("lengths"),
            strings=payload.get("strings"),
            row_ids=None if row_ids is None
            else np.asarray(row_ids, np.int32),
            done=self._loop.create_future(),
            deadline=deadline)
        conn.entries[req_id] = ent
        conn.queue.append(ent)
        self._inflight_total += 1
        self._wake.set()

    # ------------------------------------------- pool verbs (worker thread)
    def _stats_payload(self) -> dict:
        stats = self.node.pool.stats
        return {"bytes_read": stats.bytes_read,
                "bytes_written": stats.bytes_written,
                "bytes_shipped": stats.bytes_shipped,
                "requests": stats.requests,
                "dispatches": self.node.dispatches,
                "inflight": self._inflight_total,
                "shed": self._shed_total,
                "deadline_shed": self._deadline_shed_total,
                "conns": len(self._conns)}

    def _pool_verb(self, ftype: int, payload):
        """ALLOC / FREE / catalog / raw reads+writes, serialized with the
        drains on the single worker thread (the node is lock-free)."""
        node = self.node
        if ftype == wire.ALLOC:
            ft = payload["ft"]
            node.pool.alloc_table(ft)
            self._tables[ft.table_id] = ft
            return {"table_id": ft.table_id, "pages": list(ft.pages)}
        if ftype == wire.FREE:
            ft = self._tables.pop(payload["table_id"], None)
            if ft is not None:
                node.pool.free_table(ft)
            return {}
        if ftype == wire.REGISTER:
            ft = self._tables.get(payload["table_id"])
            if ft is None:
                raise fv.FarviewError(
                    f"REGISTER {payload['name']!r}: unknown table_id "
                    f"{payload['table_id']}")
            node.tables[payload["name"]] = ft
            return {}
        if ftype == wire.UNREGISTER:
            node.tables.pop(payload["name"], None)
            return {}
        ft = self._tables.get(payload["table_id"])
        if ft is None:
            raise fv.FarviewError(
                f"unknown table_id {payload['table_id']}")
        if ftype == wire.WRITE:
            node.check_fault("table_write")
            node.pool.write_table(ft, payload["data"])
            stats = node.pool.stats
            stats.bytes_written += int(
                np.asarray(payload["data"]).size) * 4
            return {}
        if ftype == wire.READ:
            node.check_fault("table_read")
            return {"data": np.asarray(node.pool.read_table(ft))}
        if ftype == wire.READ_ROWS:
            node.check_fault("table_read")
            idx = np.asarray(payload["idx"])
            return {"data": np.asarray(node.pool.read_rows(ft, idx))}
        raise wire.ProtocolError(f"unhandled pool verb {ftype}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="serve one FViewNode over TCP (docs/network.md)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (printed as LISTENING <p>)")
    ap.add_argument("--capacity-mb", type=int, default=64)
    ap.add_argument("--regions", type=int, default=6)
    ap.add_argument("--node-id", type=int, default=0)
    ap.add_argument("--queue-depth", type=int, default=1024)
    ap.add_argument("--flush-interval-ms", type=float, default=2.0)
    ap.add_argument("--log", default=None, help="append server log here")
    args = ap.parse_args(argv)
    compile_cache.enable()
    server = FViewServer(
        host=args.host, port=args.port,
        capacity_bytes=args.capacity_mb * 2**20, n_regions=args.regions,
        node_id=args.node_id, max_queue_depth=args.queue_depth,
        flush_interval_s=args.flush_interval_ms / 1e3, log_path=args.log)
    asyncio.run(server.run_forever())


if __name__ == "__main__":
    main()
