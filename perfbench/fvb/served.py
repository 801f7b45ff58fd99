"""The served path as a user reaches it, with the benchmark's own spans
and byte count around the program's layers.

An in-process `FViewServer` (its asyncio loop on a thread, its node work on
its one executor thread) and a `RemoteNodeHandle` over a localhost socket:
one process, so one process holds the chip. Nothing here changes what the
program does. The subclasses only open `TraceAnnotation` spans around the
calls into each layer. `WireCount` counts the bytes the client receives
at its socket objects, whatever part of the program reads them.
"""
from __future__ import annotations

import gc
import os
import socket

from jax.profiler import TraceAnnotation

from repro.core.client import FViewNode
from repro.net.client import RemoteNodeHandle
from repro.net.server import FViewServer


class SpannedNode(FViewNode):
    """The node; its scheduler round (submit to dispatch) as `srv.dispatch`."""

    def flush(self) -> None:
        with TraceAnnotation("srv.dispatch"):
            super().flush()


class SpannedServer(FViewServer):
    """The server; a drained batch (dispatch, device wait, finalize, result
    payload) as `srv.batch`, a frame encoded and written as `srv.send`."""

    def _run_batch(self, batch: list) -> None:
        with TraceAnnotation("srv.batch"):
            super()._run_batch(batch)

    async def _send(self, conn, ftype: int, req_id: int, obj=None) -> None:
        with TraceAnnotation("srv.send"):
            await super()._send(conn, ftype, req_id, obj)


class SpannedHandle(RemoteNodeHandle):
    """The client's handle; the SUBMIT is sent under `fv.send`, the FLUSH
    barrier waits under `fv.wait`."""

    def submit(self, *args, **kwargs):
        with TraceAnnotation("fv.send"):
            return super().submit(*args, **kwargs)

    def flush(self) -> None:
        with TraceAnnotation("fv.wait"):
            super().flush()


def start(config: dict, pool_bytes: int):
    """(server, handle) for a configuration; the caller stops both."""
    node = SpannedNode(pool_bytes, n_regions=int(config["n_regions"]))
    server = SpannedServer.start_in_thread(
        node=node, max_payload=int(config["max_payload"]),
        io_timeout_s=900.0)
    try:
        handle = SpannedHandle(server.host, server.port, timeout_s=900.0,
                                max_payload=int(config["max_payload"]))
    except Exception:
        server.stop_thread()
        raise
    return server, handle


def _counting(base: type) -> type:
    """A subclass of the socket class `base` whose receive calls add the
    bytes they return to the class's `received`. It adds no slots, so a
    socket of `base` can take it as its class in place."""
    def count(n: int) -> None:
        cls.received += n

    def recv(self, *a):
        data = base.recv(self, *a)
        count(len(data))
        return data

    def recv_into(self, *a):
        n = base.recv_into(self, *a)
        count(n)
        return n

    def recvfrom(self, *a):
        data, addr = base.recvfrom(self, *a)
        count(len(data))
        return data, addr

    def recvfrom_into(self, *a):
        n, addr = base.recvfrom_into(self, *a)
        count(n)
        return n, addr

    def recvmsg(self, *a):
        data, anc, flags, addr = base.recvmsg(self, *a)
        count(len(data))
        return data, anc, flags, addr

    def recvmsg_into(self, *a):
        n, anc, flags, addr = base.recvmsg_into(self, *a)
        count(n)
        return n, anc, flags, addr

    cls = type("CountingSocket", (base,), {
        "__slots__": (), "received": 0, "recv": recv, "recv_into": recv_into,
        "recvfrom": recvfrom, "recvfrom_into": recvfrom_into,
        "recvmsg": recvmsg, "recvmsg_into": recvmsg_into})
    return cls


class WireCount:
    """Bytes the client has received on its TCP connections to `port`: every
    frame, header and trailer included. The connections are found by the
    kernel's table (/proc/self/net/tcp), their socket objects among the
    process's objects, and each socket counts what its receive calls
    return, whatever part of the program makes them. (The kernel's own
    count, TCP_INFO's tcpi_bytes_received, reads 0 on the chip's hosts.)"""

    def __init__(self, port: int):
        self.port = port
        self._cls = _counting(socket.socket)
        self._counted: set = set()          # inodes of the adopted sockets

    def _inodes(self) -> frozenset:
        want = set()
        for name in ("tcp", "tcp6"):
            try:
                lines = open(f"/proc/self/net/{name}").read().splitlines()
            except OSError:
                continue
            for line in lines[1:]:
                f = line.split()
                # rem_address is host:port in hex; st 01 is ESTABLISHED
                if int(f[2].rsplit(":", 1)[1], 16) == self.port and \
                        f[3] == "01":
                    want.add(int(f[9]))
        return frozenset(want)

    def _adopt(self, want: frozenset) -> None:
        for obj in gc.get_objects():
            if not isinstance(obj, socket.socket) or obj.fileno() < 0:
                continue
            inode = os.fstat(obj.fileno()).st_ino
            if inode in want and inode not in self._counted:
                obj.__class__ = self._cls
                self._counted.add(inode)

    def read(self) -> tuple:
        """(the client's connections to the server, as socket inodes; the
        bytes received on every connection counted so far)."""
        # the kernel's table holds both ends; the client's end is the one
        # whose remote port is the server's
        want = self._inodes()
        if not want:
            raise RuntimeError(f"no TCP connection to port {self.port}: "
                               "the client's bytes cannot be counted")
        if want - self._counted:
            self._adopt(want)
        if want - self._counted:
            raise RuntimeError("a connection to the server has no socket "
                               "object to count its bytes")
        return want, self._cls.received
