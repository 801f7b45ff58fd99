"""Network-tier parity (PR 8 tentpole): sockets change NOTHING.

The contract: a `FarCluster` over `RemoteNodeHandle`s talking to real
`FViewServer` TCP sockets answers every Farview verb BYTE-IDENTICALLY
to the in-process cluster — selection, projection, smart addressing,
group-aggregate, distinct, regex, crypt (pre and post), join — with
the same shipped/read accounting, the same qp counters, and the same
PR 6 failover semantics across a REAL connection drop (the server's
transport is aborted, or the server process SIGKILLed, mid-stream).

Two harness modes, same tests:

  * default — servers run inside this process on daemon threads
    (`FViewServer.start_in_thread`), fast because jit caches are shared;
  * `FARVIEW_NET_SUBPROCESS=1` — every server is a REAL
    `python -m repro.net.server` subprocess and the kill tests are
    SIGKILL. The CI `server-smoke` lane runs this mode; server logs go
    to `$FARVIEW_NET_LOG_DIR` for the failure artifact. It is a CPU test
    mode: a chip belongs to one process, so on a TPU the servers run as
    threads of the process that holds it (as `chip_smoke.py` does).

Backpressure is part of the contract too: past the admission bound a
SUBMIT is answered with a typed `OVERLOADED` frame (`OverloadedError`
client-side), shed requests never half-run, and every accepted request
completes exactly.
"""
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import operators as op
from repro.core.client import (FViewNode, NodeDeadError, alloc_table_mem,
                               farview_request, merge_group_partials,
                               open_connection, table_write)
from repro.core.cluster import FarCluster
from repro.core.table import Column, FTable, string_table
from repro.distributed.health import OverloadedError
from repro.net import RemoteNodeHandle, wire
from repro.net.server import FViewServer

REPO = Path(__file__).resolve().parents[1]
USE_SUBPROCESS = os.environ.get("FARVIEW_NET_SUBPROCESS") == "1"
# NB: Path("") is a truthy PosixPath('.'), so guard on the raw string
_LOG_DIR_ENV = os.environ.get("FARVIEW_NET_LOG_DIR")
LOG_DIR = Path(_LOG_DIR_ENV) if _LOG_DIR_ENV else None

N = 500
COLS = tuple(Column(f"c{i}", "i32" if i == 0 else "f32") for i in range(6))
KEY, NONCE = (11, 22), 7
CAPACITY = 128 * 2**20


# ---------------------------------------------------------------- the harness
class _ThreadServer:
    """A server on a daemon thread in THIS process."""

    def __init__(self, node_id: int, **kw):
        kw.setdefault("capacity_bytes", CAPACITY)
        if LOG_DIR is not None:
            LOG_DIR.mkdir(parents=True, exist_ok=True)
            kw.setdefault("log_path",
                          str(LOG_DIR / f"node{node_id}-thread.log"))
        self.srv = FViewServer.start_in_thread(node_id=node_id, **kw)
        self.port = self.srv.port

    def abort(self) -> None:        # the REAL connection drop: RST every peer
        self.srv.stop_thread(abort=True)

    def stop(self) -> None:
        self.srv.stop_thread()


class _ProcServer:
    """A server as a REAL `python -m repro.net.server` subprocess."""

    def __init__(self, node_id: int, *, capacity_bytes: int = CAPACITY,
                 max_queue_depth: int = 1024,
                 flush_interval_s: float = 0.002, n_regions: int = 6):
        cmd = [sys.executable, "-m", "repro.net.server", "--port", "0",
               "--node-id", str(node_id),
               "--capacity-mb", str(capacity_bytes // 2**20),
               "--regions", str(n_regions),
               "--queue-depth", str(max_queue_depth),
               "--flush-interval-ms", str(flush_interval_s * 1e3)]
        if LOG_DIR is not None:
            LOG_DIR.mkdir(parents=True, exist_ok=True)
            cmd += ["--log", str(LOG_DIR / f"node{node_id}-{os.getpid()}-"
                                           f"{time.monotonic_ns()}.log")]
        env = dict(os.environ)
        env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        self.proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                     env=env, text=True)
        deadline = time.monotonic() + 120
        while True:
            line = self.proc.stdout.readline()
            if line.startswith("LISTENING"):
                self.port = int(line.split()[1])
                break
            if not line or time.monotonic() > deadline:
                self.proc.kill()
                raise RuntimeError("server subprocess never came up")

    def abort(self) -> None:        # SIGKILL: the kernel drops the sockets
        self.proc.kill()
        self.proc.wait(timeout=30)

    def stop(self) -> None:
        self.proc.terminate()
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=30)


def spawn_servers(n: int, **kw) -> list:
    cls = _ProcServer if USE_SUBPROCESS else _ThreadServer
    return [cls(node_id=i, **kw) for i in range(n)]


def connect(servers, **cluster_kw) -> FarCluster:
    handles = [RemoteNodeHandle("127.0.0.1", s.port, node_id=i)
               for i, s in enumerate(servers)]
    return FarCluster(nodes=handles, **cluster_kw)


@pytest.fixture(scope="module")
def trio():
    servers = spawn_servers(3)
    yield servers
    for s in servers:
        s.stop()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    d = {"c0": rng.integers(0, 13, N).astype(np.int32)}
    for i in range(1, 6):
        # integer-valued floats: merges are exact under any order
        d[f"c{i}"] = rng.integers(-50, 50, N).astype(np.float32)
    return d


def schema(name="t"):
    return FTable(name, COLS, n_rows=N)


def solo_run(pipe, words, build=None, strings=None, lengths=None,
             ft=None):
    """The in-process single-node reference every wire result must match."""
    node = FViewNode(CAPACITY)
    qp = open_connection(node)
    if build is not None:
        bft, bwords = build
        b = FTable(bft.name, bft.columns, n_rows=bft.n_rows)
        alloc_table_mem(qp, b)
        table_write(qp, b, bwords)
    part = ft if ft is not None else schema()
    part = FTable(part.name, part.columns, n_rows=part.n_rows,
                  str_width=part.str_width)
    alloc_table_mem(qp, part)
    if words is not None:
        table_write(qp, part, words)
    return farview_request(qp, part, pipe,
                           strings=strings, lengths=lengths).finalize()


def net_run(servers, pipe, words, *, partitioner="range", keys=None,
            build=None, strings=None, lengths=None, ft=None, **cluster_kw):
    """The same verb through real sockets; frees the pool pages after."""
    cl = connect(servers, partitioner=partitioner, **cluster_kw)
    cqp = cl.open_connection()
    tables = []
    try:
        if build is not None:
            bft, bwords = build
            b = FTable(bft.name, bft.columns, n_rows=bft.n_rows)
            cb = cl.alloc_table_mem(cqp, b, replicate=True)
            cl.table_write(cqp, cb, bwords)
            tables.append(cb)
        base = ft if ft is not None else schema()
        ct = cl.alloc_table_mem(cqp, base, keys=keys)
        tables.append(ct)
        if words is not None:
            cl.table_write(cqp, ct, words)
        res = cl.farview_request(cqp, ct, pipe,
                                 strings=strings, lengths=lengths).finalize()
        return res, cl, cqp
    finally:
        for t in tables:
            try:
                cl.free_table_mem(cqp, t)
            except Exception:       # noqa: BLE001 - a kill test broke nodes
                pass


def assert_rows_identical(res, ref):
    assert res.count == ref.count
    np.testing.assert_array_equal(np.asarray(res.rows), np.asarray(ref.rows))
    assert res.shipped_bytes == ref.shipped_bytes
    assert res.read_bytes == ref.read_bytes


# -------------------------------------------------------- parity, every verb
class TestWireParity:
    """Every operator kind: socket cluster == in-process solo, to the byte."""

    def test_selection_and_counters(self, trio, data):
        pipe = (op.Select((op.Predicate("c1", "<", 0.0),
                           op.Predicate("c2", ">", -20.0))),)
        words = schema().encode(data)
        ref = solo_run(pipe, words)
        res, cl, cqp = net_run(trio, pipe, words)
        assert_rows_identical(res, ref)
        # qp byte counters mirror the server's accounting exactly
        assert cqp.bytes_shipped == ref.shipped_bytes
        assert cqp.bytes_read_pool == ref.read_bytes

    def test_projection(self, trio, data):
        pipe = (op.Project(("c1", "c3")),
                op.Select((op.Predicate("c1", ">", 0.0),)))
        words = schema().encode(data)
        assert_rows_identical(net_run(trio, pipe, words,
                                      partitioner="hash",
                                      keys=data["c0"])[0],
                              solo_run(pipe, words))

    def test_smart_addressing(self, trio, data):
        pipe = (op.SmartAddress(("c2", "c5")),
                op.Select((op.Predicate("c2", "<", 10.0),)))
        words = schema().encode(data)
        assert_rows_identical(net_run(trio, pipe, words)[0],
                              solo_run(pipe, words))

    @pytest.mark.parametrize("partitioner", ["range", "hash"])
    def test_q6_scalar_aggregate(self, trio, partitioner):
        """TPC-H Q6's shape over three servers: range predicates and an
        exact sum of a product, the partials' Python ints added by the
        client; sums pass 2^53, where an f64 stops being exact."""
        rng = np.random.default_rng(6)
        cols = tuple(Column(n, "i32") for n in ("ship", "disc", "qty",
                                                "price"))
        ft = FTable("li", cols, n_rows=N)
        words = np.stack([rng.integers(0, 2557, N), rng.integers(0, 11, N),
                          rng.integers(1, 51, N),
                          rng.integers(2**23, 2**24, N)],
                         axis=1).astype(np.float32)
        words[:200, 1] = 2**24 - 1
        pipe = (op.Select((op.Predicate("ship", ">=", 731.0),
                           op.Predicate("ship", "<", 1827.0),
                           op.Predicate("disc", ">=", 5.0),
                           op.Predicate("disc", "<=", 2.0**24),
                           op.Predicate("qty", "<", 40.0))),
                op.Aggregate((("price", "disc"), ("qty",))))
        keys = words[:, 2].astype(np.int32) if partitioner == "hash" else None
        res, *_ = net_run(trio, pipe, words, partitioner=partitioner,
                          keys=keys, ft=ft)
        m = ((words[:, 0] >= 731) & (words[:, 0] < 1827)
             & (words[:, 1] >= 5) & (words[:, 2] < 40))
        rev = sum(int(p) * int(d) for p, d in words[m][:, [3, 1]])
        assert rev > 2**53
        assert res.kind == "scalars"
        assert res.scalars == {"count": int(m.sum()), "sums": (
            rev, int(words[m, 2].astype(np.int64).sum()))}
        assert res.scalars == solo_run(pipe, words, ft=ft).scalars

    def test_group_aggregate(self, trio, data):
        pipe = (op.GroupBy("c0", ("c1", "c2"), n_buckets=128),)
        words = schema().encode(data)
        ref = merge_group_partials(schema(), pipe,
                                   [solo_run(pipe, words)]).groups
        res, *_ = net_run(trio, pipe, words, partitioner="hash",
                          keys=data["c0"])
        got = res.groups
        assert set(got) == set(ref)
        for key in ref:
            for r, c in zip(ref[key], got[key]):
                np.testing.assert_array_equal(np.asarray(r), np.asarray(c))

    def test_distinct(self, trio, data):
        pipe = (op.Distinct(("c0",), n_buckets=128),)
        words = schema().encode(data)
        ref = merge_group_partials(schema(), pipe,
                                   [solo_run(pipe, words)]).groups
        res, *_ = net_run(trio, pipe, words, partitioner="hash",
                          keys=data["c0"])
        assert set(res.groups) == set(ref) == set(np.unique(data["c0"]))

    def test_crypt_pre_and_post(self, trio, data):
        import jax.numpy as jnp
        from repro.kernels import ref as kref
        words = schema().encode(data)
        flat = jnp.asarray(np.asarray(words, np.float32).reshape(-1))
        enc = np.asarray(kref.ctr_crypt(
            flat.view(jnp.uint32), jnp.asarray(KEY, jnp.uint32),
            NONCE)).view(np.float32).reshape(np.shape(words))
        pre = (op.Crypt(key=KEY, nonce=NONCE, when="pre"),
               op.Select((op.Predicate("c1", "<", 0.0),)))
        ref = solo_run(pre, enc)
        assert ref.count > 0
        assert_rows_identical(net_run(trio, pre, enc)[0], ref)
        post = (op.Select((op.Predicate("c2", ">", 0.0),)),
                op.Crypt(key=(3, 9), nonce=4, when="post"))
        assert_rows_identical(net_run(trio, post, words,
                                      partitioner="hash",
                                      keys=data["c0"])[0],
                              solo_run(post, words))

    def test_regex_strings(self, trio):
        strs = [b"error: disk full", b"all fine", b"ERROR", b"warn: error",
                b"errr", b"the error is late"]
        rng = np.random.default_rng(5)
        ft, mat, lens = string_table(
            "s", [strs[j] for j in rng.integers(0, len(strs), 300)], 24)
        pipe = (op.RegexMatch("error"),)
        ref = solo_run(pipe, None, strings=mat, lengths=lens, ft=ft)
        res, *_ = net_run(trio, pipe, None, strings=mat, lengths=lens,
                          ft=ft)
        np.testing.assert_array_equal(np.asarray(res.mask),
                                      np.asarray(ref.mask))
        assert res.shipped_bytes == ref.shipped_bytes
        assert res.read_bytes == ref.read_bytes

    def test_join_partitioned_probe(self, trio, data):
        rng = np.random.default_rng(3)
        bft = FTable("cust", (Column("k", "i32"), Column("v")), n_rows=40)
        bwords = bft.encode(
            {"k": rng.permutation(64)[:40].astype(np.int32),
             "v": rng.integers(0, 99, 40).astype(np.float32)})
        pipe = (op.JoinSmall(probe_key="c0", build_table="cust",
                             build_key="k", build_cols=("v",)),)
        jdata = dict(data)
        jdata["c0"] = rng.integers(0, 64, N).astype(np.int32)
        words = schema().encode(jdata)
        ref = solo_run(pipe, words, build=(bft, bwords))
        res, *_ = net_run(trio, pipe, words, partitioner="hash",
                          keys=jdata["c0"], build=(bft, bwords))
        assert_rows_identical(res, ref)

    def test_pool_read_roundtrip_and_stats(self, trio, data):
        """Raw table read + pool stats travel the wire exactly."""
        cl = connect(trio)
        cqp = cl.open_connection()
        words = schema().encode(data)
        ct = cl.alloc_table_mem(cqp, schema())
        try:
            cl.table_write(cqp, ct, words)
            np.testing.assert_array_equal(
                np.asarray(cl.table_read(cqp, ct), np.float32),
                np.asarray(words, np.float32))
            stats = cl.stats
            assert stats.bytes_written >= words.size * 4
        finally:
            cl.free_table_mem(cqp, ct)


# --------------------------------------------------- failover: real RST/KILL
class TestConnectionDropFailover:
    """PR 6 semantics across a REAL dead socket: the kill is a transport
    abort (thread mode) or SIGKILL (subprocess mode), never a mock."""

    def _servers(self):
        return spawn_servers(3)

    def test_selection_kill_mid_stream(self, data):
        servers = self._servers()
        try:
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            words = schema().encode(data)
            ref = solo_run(pipe, words)
            cl = connect(servers, replicas=2)
            cqp = cl.open_connection()
            ct = cl.alloc_table_mem(cqp, schema())
            cl.table_write(cqp, ct, words)
            pend = cl.submit_request(cqp, ct, pipe)
            servers[1].abort()          # dies AFTER submit, BEFORE drain
            assert_rows_identical(pend.wait(), ref)
            assert cl.health.dead_nodes() == [1]
        finally:
            for i, s in enumerate(servers):
                if i != 1:
                    s.stop()

    def test_group_aggregate_kill_mid_stream(self, data):
        servers = self._servers()
        try:
            pipe = (op.GroupBy("c0", ("c1",), n_buckets=128),)
            words = schema().encode(data)
            ref = merge_group_partials(schema(), pipe,
                                       [solo_run(pipe, words)]).groups
            cl = connect(servers, replicas=2, partitioner="hash")
            cqp = cl.open_connection()
            ct = cl.alloc_table_mem(cqp, schema(), keys=data["c0"])
            cl.table_write(cqp, ct, words)
            pend = cl.submit_request(cqp, ct, pipe)
            servers[0].abort()
            got = pend.wait().groups
            assert set(got) == set(ref)
            for key in ref:
                for r, c in zip(ref[key], got[key]):
                    np.testing.assert_array_equal(np.asarray(r),
                                                  np.asarray(c))
            assert cl.health.dead_nodes() == [0]
        finally:
            for i, s in enumerate(servers):
                if i != 0:
                    s.stop()

    def test_table_read_fails_over(self, data):
        servers = self._servers()
        try:
            words = schema().encode(data)
            cl = connect(servers, replicas=2)
            cqp = cl.open_connection()
            ct = cl.alloc_table_mem(cqp, schema())
            cl.table_write(cqp, ct, words)
            servers[2].abort()
            np.testing.assert_array_equal(
                np.asarray(cl.table_read(cqp, ct), np.float32),
                np.asarray(words, np.float32))
            assert 2 in cl.health.dead_nodes()
        finally:
            for i, s in enumerate(servers):
                if i != 2:
                    s.stop()

    def test_dead_connect_raises_node_dead(self):
        with socket.socket() as s:      # grab a port nobody serves
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        with pytest.raises(NodeDeadError):
            RemoteNodeHandle("127.0.0.1", port, node_id=0, timeout_s=2)


# ------------------------------------------------------------- backpressure
class TestBackpressure:
    def test_overload_sheds_typed_and_accepted_complete(self, data):
        """Past the admission bound: typed OVERLOADED (never a hang,
        never a half-run); every admitted request completes exactly."""
        servers = spawn_servers(1, max_queue_depth=4,
                                flush_interval_s=0.25)
        try:
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            words = schema().encode(data)
            ref = solo_run(pipe, words)
            node = RemoteNodeHandle("127.0.0.1", servers[0].port,
                                    node_id=0)
            qp = node.open_connection()
            ft = schema()
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, words)
            pends = [node.submit(qp, ft, pipe) for _ in range(12)]
            shed = completed = 0
            for pend in pends:
                try:
                    res = pend.wait()
                except OverloadedError as e:
                    shed += 1
                    assert e.node_id == 0
                    assert "share" in e.detail or "depth" in e.detail
                else:
                    completed += 1
                    assert_rows_identical(res, ref)
            assert shed >= 1            # the bound actually bit
            assert completed >= 1       # and admitted work finished
            assert shed + completed == 12
            node.close()
        finally:
            servers[0].stop()

    def test_stats_reply_carries_queue_wait(self, data):
        """`STATS` reports each picked request's admission-to-pick wait:
        with the FLUSH sent after the batching window has run, the one
        request waited the whole window."""
        servers = spawn_servers(1, flush_interval_s=0.1)
        try:
            node = RemoteNodeHandle("127.0.0.1", servers[0].port,
                                    node_id=0)
            stats = node.server_stats()
            assert stats["queued"] == 0 and stats["queue_wait_s"] == 0.0
            qp = node.open_connection()
            ft = schema()
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, schema().encode(data))
            pend = node.submit(qp, ft, (op.Select(
                (op.Predicate("c1", "<", 0.0),)),))
            time.sleep(0.3)
            pend.wait()
            stats = node.server_stats()
            assert stats["queued"] == 1
            assert stats["queue_wait_s"] >= 0.09
            node.close()
        finally:
            servers[0].stop()


# ------------------------------------------- robustness against a live server
class TestLiveProtocolRobustness:
    def test_garbage_poisons_one_conn_not_the_server(self, trio):
        """Garbage bytes get a typed ERROR and THAT conn dropped; a
        well-behaved client on the same server is unaffected."""
        raw = socket.create_connection(("127.0.0.1", trio[0].port),
                                       timeout=30)
        raw.sendall(b"\xde\xad\xbe\xef" * 8)
        hdr = b""
        while len(hdr) < wire.HEADER_SIZE:
            chunk = raw.recv(wire.HEADER_SIZE - len(hdr))
            if not chunk:
                break
            hdr += chunk
        assert len(hdr) == wire.HEADER_SIZE
        ftype, _, length = wire.parse_header(hdr)
        assert ftype == wire.ERROR
        body = b""
        while len(body) < length:
            body += raw.recv(length - len(body))
        err = wire.decode_error(wire.decode_value(body))
        assert isinstance(err, wire.ProtocolError)
        trailer = b""
        while len(trailer) < wire.TRAILER_SIZE:
            chunk = raw.recv(wire.TRAILER_SIZE - len(trailer))
            if not chunk:
                break
            trailer += chunk
        wire.check_crc(hdr, body, trailer)      # server frames carry CRC
        assert raw.recv(1) == b""       # and the poisoned conn is dropped
        raw.close()
        # the server is still fully alive for everyone else
        node = RemoteNodeHandle("127.0.0.1", trio[0].port, node_id=0)
        assert node.dispatches >= 0
        node.close()

    def test_oversized_frame_rejected_typed(self, trio):
        raw = socket.create_connection(("127.0.0.1", trio[0].port),
                                       timeout=30)
        raw.sendall(wire.HEADER.pack(wire.MAGIC, wire.VERSION, wire.SUBMIT,
                                     1, wire.MAX_PAYLOAD + 1))
        hdr = raw.recv(wire.HEADER_SIZE)
        ftype, _, length = wire.parse_header(hdr)
        assert ftype == wire.ERROR
        body = b""
        while len(body) < length:
            body += raw.recv(length - len(body))
        assert isinstance(wire.decode_error(wire.decode_value(body)),
                          wire.ProtocolError)
        wire.check_crc(hdr, body,
                       raw.recv(wire.TRAILER_SIZE, socket.MSG_WAITALL))
        raw.close()

    def test_version_mismatch_is_typed(self, trio):
        raw = socket.create_connection(("127.0.0.1", trio[0].port),
                                       timeout=30)
        raw.sendall(wire.encode_frame(wire.HELLO, 1, {"version": 99}))
        hdr = raw.recv(wire.HEADER_SIZE)
        ftype, _, length = wire.parse_header(hdr)
        assert ftype == wire.ERROR
        body = b""
        while len(body) < length:
            body += raw.recv(length - len(body))
        err = wire.decode_error(wire.decode_value(body))
        assert isinstance(err, wire.ProtocolError)
        assert "version" in str(err)
        wire.check_crc(hdr, body,
                       raw.recv(wire.TRAILER_SIZE, socket.MSG_WAITALL))
        raw.close()
