"""`RemoteNodeHandle`: the socket transport that duck-types `FViewNode`.

`FarCluster` talks to a node through a narrow surface — `submit` /
`flush` / `settle` / `has_queued` / `open_connection` / `tables` /
`pool` — and this class implements exactly that surface over one TCP
connection speaking `net/wire.py` frames, so the scatter-gather merge,
PR 6 failover and PR 5 rebalancing run UNCHANGED over sockets:

  * `submit` ships the verb immediately as a `SUBMIT` frame (the
    server admits or sheds, and batches admitted verbs into its node's
    scheduler rounds); the returned `RemotePending` mirrors
    `PendingRequest` (`.result` / `.error` / `.wait()`).
  * `flush` sends the `FLUSH` barrier and absorbs `RESULT` / typed
    `ERROR` frames until the server acks — each result rebuilds as an
    ALREADY-FINALIZED `PipelineResult` from wire arrays, which is all
    `offload._merge` reads, so merges are byte-identical to in-process.
  * any socket death (reset, EOF, timeout) becomes
    `NodeDeadError(node_id)` on every in-flight verb — the same typed
    error an in-process killed node raises — so `ClusterPending`
    reroutes to a replica across a REAL connection drop and the health
    monitor marks the node DEAD, exactly as PR 6 specified.

Send failures inside `submit` do NOT raise: they attach the
`NodeDeadError` to the pending (like an in-process dispatch-time
fault), because failover resolves mid-flight in `wait()`, not at
submit. Catalog maintenance (`tables[...]` / `.pop`) on a dead node is
best-effort — the node's catalog died with it; the cluster-side heal
rebuilds elsewhere.
"""
from __future__ import annotations

import itertools
import socket
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import client as fv
from repro.core import operators as op_ir
from repro.core.pipeline import PipelineResult
from repro.core.pool import PoolStats
from repro.distributed.health import CircuitBreaker
from repro.net import wire


class RemoteQPair:
    """Client-side view of a server virtual QPair: same id/counter
    surface as `fv.QPair`; byte counters mirror the server's accounting
    from each RESULT frame and settle through the handle."""

    def __init__(self, node: "RemoteNodeHandle", vqp: int, region: int):
        self.qp_id = vqp
        self.vqp = vqp
        self.node = node
        self.region = region
        self.requests = 0
        self._bytes_shipped = 0
        self._bytes_read_pool = 0

    @property
    def bytes_shipped(self) -> int:
        self.node.settle()
        return self._bytes_shipped

    @property
    def bytes_read_pool(self) -> int:
        self.node.settle()
        return self._bytes_read_pool


class RemotePending:
    """Mirror of `fv.PendingRequest` for a wire-submitted verb."""

    def __init__(self, node: "RemoteNodeHandle", qp: RemoteQPair,
                 req_id: int, ft):
        self.node = node
        self.qp = qp
        self.req_id = req_id
        self.ft = ft
        self.result: PipelineResult | None = None
        self.error: Exception | None = None

    def _attach(self, payload: dict) -> None:
        with TraceAnnotation("fv.attach"):
            rows = payload.get("rows")
            if "n_rows" in payload:     # survivors only: restore the tail
                full = np.zeros((int(payload["n_rows"]),) + rows.shape[1:],
                                rows.dtype)
                full[: rows.shape[0]] = rows
                rows = full
            res = PipelineResult(
                payload["kind"], rows=rows,
                count=payload.get("count"), groups=payload.get("groups"),
                mask=payload.get("mask"), scalars=payload.get("scalars"),
                shipped_bytes=int(payload.get("shipped", 0)),
                read_bytes=int(payload.get("read", 0)),
                sel_ids=payload.get("sel_ids"))
        self.result = res
        self.qp.requests += 1
        self.qp._bytes_shipped += int(payload.get("shipped", 0))
        self.qp._bytes_read_pool += int(payload.get("read", 0))

    def wait(self) -> PipelineResult:
        if self.result is None and self.error is None:
            try:
                self.node.flush()
            except Exception:
                # another request's failure; ours may have resolved fine
                if self.result is None and self.error is None:
                    raise
        if self.error is not None:
            raise self.error
        return self.result.finalize()


class RemoteCatalog:
    """The node catalog (`name -> FTable`) over REGISTER/UNREGISTER
    frames, with a local mirror for reads. Best-effort on a dead node:
    its catalog is gone anyway, and cluster alias refreshes must not
    wedge a heal on an unreachable server."""

    def __init__(self, node: "RemoteNodeHandle"):
        self._node = node
        self._local: dict = {}

    def __setitem__(self, name: str, ft) -> None:
        self._local[name] = ft
        try:
            self._node._call(wire.REGISTER,
                             {"name": name, "table_id": ft.table_id},
                             op="register")
        except fv.NodeDeadError:
            pass

    def pop(self, name: str, default=None):
        out = self._local.pop(name, default)
        try:
            self._node._call(wire.UNREGISTER, {"name": name},
                             op="unregister")
        except fv.NodeDeadError:
            pass
        return out

    def __getitem__(self, name: str):
        return self._local[name]

    def get(self, name: str, default=None):
        return self._local.get(name, default)

    def __contains__(self, name: str) -> bool:
        return name in self._local

    def __len__(self) -> int:
        return len(self._local)


class RemotePool:
    """The `FarPool` verb surface over ALLOC/FREE/WRITE/READ frames.
    Placement (`table_id`, `pages`) is stamped by the SERVER's pool;
    the client-side FTable handle just records it."""

    def __init__(self, node: "RemoteNodeHandle"):
        self._node = node
        self._last_stats = PoolStats()

    def alloc_table(self, ft):
        resp = self._node._call(wire.ALLOC, {"ft": ft}, op="alloc")
        ft.table_id = int(resp["table_id"])
        ft.pages = tuple(int(p) for p in resp["pages"])
        return ft

    def free_table(self, ft) -> None:
        self._node._call(wire.FREE, {"table_id": ft.table_id}, op="free")

    def write_table(self, ft, words) -> None:
        self._node._call(
            wire.WRITE,
            {"table_id": ft.table_id,
             "data": np.asarray(words, np.float32)}, op="table_write")

    def read_table(self, ft):
        return self._node._call(wire.READ, {"table_id": ft.table_id},
                                op="table_read")["data"]

    def read_rows(self, ft, row_idx):
        return self._node._call(
            wire.READ_ROWS,
            {"table_id": ft.table_id, "idx": np.asarray(row_idx)},
            op="table_read")["data"]

    # ---- tiering (PR 10): the tier lives in the SERVER's pool. The
    # server-side read/submit paths note accesses and bill compressed
    # physical bytes against their own ledgers; over the socket the
    # DECODED rows are what ships, so this hop legitimately bills
    # logical bytes and never sees a tier bit.
    def is_tiered(self, ft) -> bool:
        return False

    def note_access(self, ft) -> bool:
        return False

    def tier_read_bytes(self, ft, col_idx=None) -> int:
        if col_idx is None:
            return ft.n_bytes
        return ft.n_rows * len(col_idx) * 4

    @property
    def stats(self) -> PoolStats:
        try:
            raw = self._node.server_stats()
        except fv.NodeDeadError:
            return self._last_stats      # last observation of a dead node
        self._last_stats = PoolStats(
            bytes_read=int(raw["bytes_read"]),
            bytes_written=int(raw["bytes_written"]),
            bytes_shipped=int(raw["bytes_shipped"]),
            requests=int(raw["requests"]))
        return self._last_stats


class RemoteNodeHandle:
    """One TCP connection to a `FViewServer`, presenting the
    `FViewNode` duck type (see module docstring)."""

    def __init__(self, host: str, port: int, *, node_id: int = 0,
                 timeout_s: float = 120.0,
                 max_payload: int = wire.MAX_PAYLOAD,
                 reconnect: bool = True,
                 reconnect_attempts: int = 3,
                 reconnect_backoff_s: float = 0.05,
                 reconnect_reset_s: float = 0.5):
        self.host = host
        self.port = port
        self.node_id = node_id
        self.timeout_s = float(timeout_s)
        self.max_payload = int(max_payload)
        self.reconnect = bool(reconnect)
        self.reconnect_attempts = int(reconnect_attempts)
        self.reconnect_backoff_s = float(reconnect_backoff_s)
        # gates reconnection so a down server is probed, not hammered:
        # one failed reconnect cycle trips OPEN (fast-fail verbs), and
        # after reset_after_s a single HALF_OPEN probe retries.
        self._breaker = CircuitBreaker(
            1, open_after=1, reset_after_s=float(reconnect_reset_s))
        self._closed = False
        self._ever_connected = False
        # serializes the socket: cluster drain threads, settle-on-read
        # counters and catalog calls may interleave. RLock because
        # settle -> flush -> _recv re-enter through property reads.
        self._lock = threading.RLock()
        self._req_ids = itertools.count(1)
        self._pending: dict[int, RemotePending] = {}    # guarded-by: self._lock
        self._qpairs: dict[int, RemoteQPair] = {}
        self._dead = False
        self._sock: socket.socket | None = None
        self.tables = RemoteCatalog(self)
        self.pool = RemotePool(self)
        self._connect()

    # ------------------------------------------------------------ transport
    def _connect(self) -> None:
        try:
            self._sock = socket.create_connection(
                (self.host, self.port), timeout=self.timeout_s)
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError as e:
            self._dead = True
            raise fv.NodeDeadError(self.node_id, op="connect") from e
        # version handshake: a mismatched server answers with a typed
        # ProtocolError frame instead of mis-decoding every later verb
        self._call(wire.HELLO, {"version": wire.VERSION},
                   op="hello", expect=wire.HELLO_OK)
        self._ever_connected = True

    def _reopen_qpairs(self) -> None:
        """Re-establish virtual QPairs on a freshly reconnected server,
        keeping the client-side `RemoteQPair` objects (and their byte
        counters) that callers hold references to."""
        old = list(self._qpairs.values())
        self._qpairs = {}
        for qp in old:
            resp = self._call(wire.OPEN_QP, {}, op="reconnect")
            qp.vqp = qp.qp_id = int(resp["qp"])
            qp.region = qp.vqp % max(1, int(resp.get("region_count", 1)))
            self._qpairs[qp.vqp] = qp

    def _ensure_conn(self, op: str) -> None:
        """Bounded reconnect-with-backoff behind the breaker: a server
        that was restarted resumes service on the next verb without a
        cluster-level heal; a server that stays down fast-fails while
        the breaker is OPEN and is re-probed once per reset window.
        Only a handle that connected successfully at least once
        reconnects — construction against a bad endpoint stays a
        fast, typed failure."""
        with self._lock:
            if not self._dead and self._sock is not None:
                return
            if (self._closed or not self.reconnect
                    or not self._ever_connected):
                raise fv.NodeDeadError(self.node_id, op=op)
            if not self._breaker.allow(0):
                raise fv.NodeDeadError(self.node_id, op=op)
            delay = self.reconnect_backoff_s
            last: Exception | None = None
            for attempt in range(self.reconnect_attempts):
                try:
                    self._dead = False
                    self._connect()
                    self._reopen_qpairs()
                except (fv.NodeDeadError, wire.ProtocolError, OSError) as e:
                    last = e
                    self._dead = True
                    if attempt + 1 < self.reconnect_attempts:
                        time.sleep(delay)
                        delay *= 2
                    continue
                self._breaker.record_success(0)
                return
            self._breaker.record_failure(0)
            raise fv.NodeDeadError(self.node_id, op=op) from last

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._dead = True
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None

    def _die(self, op: str) -> fv.NodeDeadError:
        """The socket is gone: every in-flight verb fails typed."""
        err = fv.NodeDeadError(self.node_id, op=op)
        self._dead = True
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        with self._lock:        # re-entrant: callers already hold it
            for pend in self._pending.values():
                if pend.error is None and pend.result is None:
                    pend.error = err
            self._pending.clear()
        return err

    def _send_frame(self, ftype: int, req_id: int, obj, *,
                    op: str) -> None:
        if self._dead or self._sock is None:
            self._ensure_conn(op)
        try:
            self._sock.sendall(wire.encode_frame(
                ftype, req_id, obj, max_payload=self.max_payload))
        except (OSError, ValueError) as e:
            raise self._die(op) from e

    def _recv_exact(self, n: int, *, op: str) -> bytes:
        chunks = []
        while n:
            try:
                chunk = self._sock.recv(n)
            except (OSError, ValueError) as e:      # reset / timeout / closed
                raise self._die(op) from e
            if not chunk:                           # orderly EOF mid-frame
                raise self._die(op)
            chunks.append(chunk)
            n -= len(chunk)
        return b"".join(chunks)

    def _recv_frame(self, *, op: str):
        hdr = self._recv_exact(wire.HEADER_SIZE, op=op)
        try:
            ftype, req_id, length = wire.parse_header(
                hdr, max_payload=self.max_payload)
            with TraceAnnotation("fv.recv"):
                body = self._recv_exact(length, op=op) if length else b""
                trailer = self._recv_exact(wire.TRAILER_SIZE, op=op)
            # integrity before trust: a corrupted frame fails typed here
            # and POISONS the stream (no resync point exists) — the node
            # reads as dead and failover reroutes, never wrong bytes
            with TraceAnnotation("fv.crc"):
                wire.check_crc(hdr, body, trailer)
        except wire.ProtocolError as e:
            raise self._die(op) from e
        if not length:
            return ftype, req_id, None
        with TraceAnnotation("fv.decode"):
            return ftype, req_id, wire.decode_value(body)

    def _absorb(self, ftype: int, req_id: int, payload) -> None:
        """Route a response frame to its in-flight verb."""
        with self._lock:        # re-entrant: callers already hold it
            pend = self._pending.pop(req_id, None)
        if pend is None:
            return                      # verb already failed locally
        if ftype == wire.RESULT:
            pend._attach(payload)
        elif ftype == wire.ERROR:
            pend.error = wire.decode_error(payload)
        elif ftype == wire.OVERLOADED:
            pend.error = wire.decode_error(
                {"code": wire.E_OVERLOADED, **(payload or {})})
        else:
            pend.error = wire.ProtocolError(
                f"unexpected {wire.FRAME_NAMES.get(ftype, ftype)!r} "
                f"reply for request {req_id}")

    def _call(self, ftype: int, obj, *, op: str, expect: int = wire.OK):
        """Synchronous request/response; absorbs any interleaved
        SUBMIT responses that arrive first."""
        with self._lock:
            req_id = next(self._req_ids)
            self._send_frame(ftype, req_id, obj, op=op)
            while True:
                rtype, rid, payload = self._recv_frame(op=op)
                if rid == req_id:
                    if rtype == expect:
                        return payload
                    if rtype == wire.ERROR:
                        raise wire.decode_error(payload)
                    if rtype == wire.OVERLOADED:
                        raise wire.decode_error(
                            {"code": wire.E_OVERLOADED, **(payload or {})})
                    raise wire.ProtocolError(
                        f"unexpected {wire.FRAME_NAMES.get(rtype, rtype)!r}"
                        f" reply to {wire.FRAME_NAMES.get(ftype, ftype)}")
                if rid == 0 and rtype == wire.ERROR:
                    # connection-poisoning error (bad frame we sent)
                    raise wire.decode_error(payload)
                self._absorb(rtype, rid, payload)

    # ------------------------------------------------- FViewNode duck type
    def check_fault(self, op: str = "dispatch") -> None:
        """Faults live server-side; a dead server surfaces as socket
        death (`NodeDeadError`) on the next verb instead."""

    @property
    def has_queued(self) -> bool:
        with self._lock:
            return bool(self._pending)

    @property
    def dispatches(self) -> int:
        try:
            return int(self.server_stats()["dispatches"])
        except fv.NodeDeadError:
            return 0

    def server_stats(self) -> dict:
        """The server's `STATS` reply: pool counters, dispatches, queue
        depth, sheds, and the queue wait of the requests it has picked
        (`queue_wait_s` summed over `queued`)."""
        return self._call(wire.STATS, {}, op="stats")

    def open_connection(self) -> RemoteQPair:
        resp = self._call(wire.OPEN_QP, {}, op="open_connection")
        vqp = int(resp["qp"])
        qp = RemoteQPair(self, vqp, region=vqp % max(
            1, int(resp.get("region_count", 1))))
        self._qpairs[vqp] = qp
        return qp

    def close_connection(self, qp: RemoteQPair) -> None:
        self._qpairs.pop(qp.vqp, None)
        with self._lock:
            for rid, pend in list(self._pending.items()):
                if pend.qp is qp:
                    pend.error = fv.FarviewError(
                        f"connection qp{qp.vqp} closed with request "
                        "pending")
                    self._pending.pop(rid, None)
        try:
            self._call(wire.CLOSE_QP, {"qp": qp.vqp}, op="close")
        except fv.NodeDeadError:
            pass                        # the server died first; same outcome

    def submit(self, qp: RemoteQPair, ft, pipeline: tuple, *,
               lengths=None, strings=None, row_ids=None,
               deadline_s: float | None = None) -> RemotePending:
        with self._lock:
            if self._dead or self._sock is None:
                # reconnect BEFORE building the payload: a successful
                # reconnect re-numbers every vqp (`_reopen_qpairs`), and
                # the frame must carry the fresh id
                try:
                    self._ensure_conn("submit")
                except fv.NodeDeadError as e:
                    pend = RemotePending(self, qp, next(self._req_ids), ft)
                    pend.error = e      # resolved by failover in wait()
                    return pend
        if qp.vqp not in self._qpairs:
            raise fv.FarviewError(f"connection qp{qp.vqp} is closed")
        pipeline = op_ir.validate_pipeline(tuple(pipeline))
        payload = {
            "qp": qp.vqp, "table_id": ft.table_id, "pipeline": pipeline,
            "lengths": None if lengths is None
            else np.asarray(lengths, np.int32),
            "strings": None if strings is None
            else np.asarray(strings, np.uint8),
            "row_ids": None if row_ids is None
            else np.asarray(row_ids, np.int32),
            # relative budget (ms): survives unsynchronized clocks; the
            # server re-anchors it on its own monotonic clock on arrival
            "deadline_ms": None if deadline_s is None
            else float(deadline_s) * 1e3}
        with self._lock:
            req_id = next(self._req_ids)
            pend = RemotePending(self, qp, req_id, ft)
            try:
                self._send_frame(wire.SUBMIT, req_id, payload, op="submit")
            except fv.NodeDeadError as e:
                # dispatch-time fault, resolved by failover in wait()
                pend.error = e
                return pend
            self._pending[req_id] = pend
        return pend

    def flush(self) -> None:
        """The FLUSH barrier: every in-flight verb resolves (RESULT or
        typed error) before this returns; the first error re-raises,
        matching `FViewNode.flush` so cluster drains and heartbeats are
        oblivious to the socket."""
        with self._lock:
            if not self._pending:
                return
            if self._dead or self._sock is None:
                try:
                    self._ensure_conn("flush")
                except fv.NodeDeadError:
                    raise self._die("flush") from None
            inflight = list(self._pending.values())
            req_id = next(self._req_ids)
            self._send_frame(wire.FLUSH, req_id, {}, op="flush")
            while True:
                rtype, rid, payload = self._recv_frame(op="flush")
                if rid == req_id:
                    if rtype == wire.OK:
                        break
                    if rtype == wire.ERROR:
                        raise wire.decode_error(payload)
                    raise wire.ProtocolError(f"bad FLUSH ack {rtype}")
                self._absorb(rtype, rid, payload)
            for pend in inflight:
                if pend.result is None and pend.error is None:
                    pend.error = fv.FarviewError(
                        "request was not resolved by the server's flush")
                self._pending.pop(pend.req_id, None)
        first = next((p.error for p in inflight if p.error is not None),
                     None)
        if first is not None:
            raise first

    def settle(self) -> None:
        """Results arrive finalized; settling is just the barrier."""
        try:
            self.flush()
        except Exception:               # noqa: BLE001
            pass        # errors stay on their RemotePendings (like a node)


def remote_cluster(endpoints, **cluster_kw):
    """`FarCluster` over running servers: `endpoints` is a list of
    (host, port); handle i becomes cluster node i. Everything above the
    node interface — partition maps, replicas, failover, rebalancing —
    is untouched."""
    from repro.core.cluster import FarCluster
    handles = [RemoteNodeHandle(host, port, node_id=i)
               for i, (host, port) in enumerate(endpoints)]
    return FarCluster(nodes=handles, **cluster_kw)
