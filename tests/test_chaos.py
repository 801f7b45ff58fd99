"""Chaos soak (PR 9 tentpole): seeded socket faults change NOTHING.

The contract extends PR 8's parity claim to a HOSTILE network. Every
Farview verb, run through `ChaosProxy` — a seeded socket-level fault
injector sitting between every `RemoteNodeHandle` and its
`FViewServer` — still answers BYTE-IDENTICALLY to the in-process
reference, or fails TYPED. There is no third outcome: a corrupted
frame fails the CRC trailer, poisons exactly that connection, and
failover reroutes to the partition's replica; a mid-frame reset or
one-way partition reads as a dead node; a duplicated frame is absorbed
by request-id correlation. Wrong bytes never escape.

Time is part of the contract too (the paper's operator off-loading
only pays if the tail is bounded):

  * deadlines — a request carries a RELATIVE budget over the wire; the
    server sheds expired work before dispatch with a typed
    `DEADLINE_EXCEEDED`, never half-running it, and a cluster query's
    budget decays across its scatter legs instead of resetting.
  * hedges — a primary that exceeds `slow_after_s` mid-flight gets its
    partition re-issued on the cyclic replica; first answer wins
    (byte-identical by construction — results are keyed by captured
    row indices), the primary wins ties.
  * breakers — a node that keeps failing trips a per-node circuit
    breaker OPEN; after the reset window ONE half-open probe decides
    whether service resumes. `RemoteNodeHandle` reconnects through the
    same gate, so a restarted server resumes WITHOUT a cluster heal.

Runs in both PR 8 harness modes (in-thread servers by default,
`FARVIEW_NET_SUBPROCESS=1` for real subprocesses). docs/chaos.md has
the fault vocabulary; benchmarks/bench_chaos.py is the soak's
latency-tail twin.
"""
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import test_network as tn
from repro.core import operators as op
from repro.core.client import (DeadlineExceededError, FarviewError,
                               FViewNode, NodeDeadError,
                               merge_group_partials, open_connection)
from repro.core.cluster import FarCluster
from repro.core.table import Column, FTable, string_table
from repro.distributed.health import (ALIVE, CLOSED, HALF_OPEN, OPEN,
                                      CircuitBreaker, HealthMonitor)
from repro.net import RemoteNodeHandle, wire
from repro.net.chaos import (CLEAN, ChaosProxy, FaultSchedule,
                             proxied_endpoints)
from repro.net.server import FViewServer

N = tn.N
KEY, NONCE = tn.KEY, tn.NONCE

# the soak schedule: jittered delivery, occasional bit flips and
# duplicated frames — enough to exercise every recovery path without
# killing both replicas of a partition in one query too often
SOAK = FaultSchedule(jitter_s=0.002, corrupt_prob=0.03,
                     duplicate_prob=0.05)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    d = {"c0": rng.integers(0, 13, N).astype(np.int32)}
    for i in range(1, 6):
        d[f"c{i}"] = rng.integers(-50, 50, N).astype(np.float32)
    return d


# ---------------------------------------------------------------- helpers
def chaos_cluster(servers, *, seed=0, schedule=None, replicas=2,
                  **cluster_kw):
    """A FarCluster whose every connection crosses a ChaosProxy."""
    proxies, endpoints = proxied_endpoints(servers, seed=seed,
                                           schedule=schedule)
    handles = [RemoteNodeHandle(h, p, node_id=i, timeout_s=60.0,
                                reconnect_backoff_s=0.02,
                                reconnect_reset_s=0.05)
               for i, (h, p) in enumerate(endpoints)]
    return FarCluster(nodes=handles, replicas=replicas,
                      **cluster_kw), proxies


def _teardown(cl, proxies, servers):
    for p in proxies or ():
        try:
            p.stop_thread()
        except Exception:       # noqa: BLE001 - a fault test wrecked it
            pass
    for s in servers or ():
        try:
            s.stop()
        except Exception:       # noqa: BLE001
            pass


def _revive_all(cl):
    for i in range(cl.n_nodes):
        cl.health.revive(i)


def run_under_chaos(cl, fn, attempts=8):
    """Retry `fn` through typed faults only. A parity violation (wrong
    bytes) raises AssertionError and is NEVER retried — chaos may cost
    retries, never correctness. Deadline sheds re-raise: time ran out."""
    last = None
    for _ in range(attempts):
        try:
            return fn()
        except DeadlineExceededError:
            raise
        except FarviewError as e:
            last = e
            _revive_all(cl)
            time.sleep(0.06)    # let handle breakers reach HALF_OPEN
    raise last


# ------------------------------------------------- parity under the soak
class TestChaosParity:
    """Every verb, through faulty sockets, at 2 and 4 nodes: byte parity
    or a typed error — never silently wrong results."""

    @pytest.mark.parametrize("n_nodes", [2, 4])
    def test_every_verb_byte_identical(self, n_nodes, data):
        servers = tn.spawn_servers(n_nodes)
        cl = proxies = None
        try:
            cl, proxies = chaos_cluster(
                servers, seed=100 + n_nodes, partitioner="hash",
                replicas=2, dead_after=2)
            cqp = cl.open_connection()
            words = tn.schema().encode(data)

            # build table for the co-partitioned join: replicated
            # everywhere, keyed on the probe table's partition key
            rng = np.random.default_rng(7)
            bft = FTable("cust", (Column("k", "i32"), Column("v")),
                         n_rows=13)
            bwords = bft.encode(
                {"k": np.arange(13, dtype=np.int32),
                 "v": rng.integers(0, 99, 13).astype(np.float32)})
            strs = [b"error: disk full", b"all fine", b"ERROR",
                    b"warn: error", b"errr", b"the error is late"]
            sft, mat, lens = string_table(
                "s", [strs[j] for j in rng.integers(0, len(strs), 300)],
                24)

            # setup runs CLEAN: chaos targets queries, not ingest
            ct = cl.alloc_table_mem(cqp, tn.schema(), keys=data["c0"])
            cl.table_write(cqp, ct, words)
            # CO-PARTITIONED build: each shard lands where the probe
            # table's hash rule put its key, so joins resolve locally
            cb = cl.alloc_table_mem(cqp, bft, co_partition=ct,
                                    keys=np.arange(13, dtype=np.int32))
            cl.table_write(cqp, cb, bwords)
            st = cl.alloc_table_mem(cqp, sft, partitioner="range")

            sel = (op.Select((op.Predicate("c1", "<", 0.0),
                              op.Predicate("c2", ">", -20.0))),)
            grp = (op.GroupBy("c0", ("c1", "c2"), n_buckets=128),)
            crypt = (op.Select((op.Predicate("c2", ">", 0.0),)),
                     op.Crypt(key=(3, 9), nonce=4, when="post"))
            rgx = (op.RegexMatch("error"),)
            join = (op.JoinSmall(probe_key="c0", build_table="cust",
                                 build_key="k", build_cols=("v",)),)

            refs = {
                "sel": tn.solo_run(sel, words),
                "grp": merge_group_partials(
                    tn.schema(), grp, [tn.solo_run(grp, words)]).groups,
                "crypt": tn.solo_run(crypt, words),
                "rgx": tn.solo_run(rgx, None, strings=mat, lengths=lens,
                                   ft=sft),
                "join": tn.solo_run(join, words, build=(bft, bwords)),
            }

            for p in proxies:           # chaos ON
                p.set_schedule(SOAK)

            for name, table, pipe, kw in (
                    ("sel", ct, sel, {}),
                    ("grp", ct, grp, {}),
                    ("crypt", ct, crypt, {}),
                    ("rgx", st, rgx,
                     {"strings": mat, "lengths": lens}),
                    ("join", ct, join, {})):
                res = run_under_chaos(
                    cl, lambda t=table, p=pipe, k=kw:
                    cl.farview_request(cqp, t, p, **k).finalize())
                if name == "grp":
                    got = res.groups
                    assert set(got) == set(refs["grp"])
                    for key in refs["grp"]:
                        for r, c in zip(refs["grp"][key], got[key]):
                            np.testing.assert_array_equal(
                                np.asarray(r), np.asarray(c))
                elif name == "rgx":
                    np.testing.assert_array_equal(
                        np.asarray(res.mask),
                        np.asarray(refs["rgx"].mask))
                    assert res.shipped_bytes == refs["rgx"].shipped_bytes
                else:
                    tn.assert_rows_identical(res, refs[name])

            # the soak actually injected faults (seeded: deterministic)
            assert any(p.fault_log for p in proxies)
        finally:
            _teardown(cl, proxies, servers)


# ------------------------------------------------------------- deadlines
class TestDeadlines:
    """A budget of zero (or one spent in a queue) sheds TYPED — the
    request never half-runs, and sheds are not health strikes."""

    def test_in_process_shed_at_flush_pick(self):
        node = FViewNode(tn.CAPACITY)
        qp = open_connection(node)
        ft = tn.schema()
        node.pool.alloc_table(ft)
        pend = node.submit(qp, ft, (op.Select(
            (op.Predicate("c1", "<", 0.0),)),), deadline_s=0.0)
        with pytest.raises(DeadlineExceededError):
            pend.wait()

    def test_expired_budget_shed_at_server_admission(self, data):
        servers = tn.spawn_servers(1)
        try:
            node = RemoteNodeHandle("127.0.0.1", servers[0].port,
                                    node_id=0)
            qp = node.open_connection()
            ft = tn.schema()
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, tn.schema().encode(data))
            pend = node.submit(qp, ft, (op.Select(
                (op.Predicate("c1", "<", 0.0),)),), deadline_s=0.0)
            with pytest.raises(DeadlineExceededError, match="arrival"):
                pend.wait()
            # the shed was typed, not a transport fault: the conn lives
            assert node.submit(qp, ft, (op.Select(
                (op.Predicate("c1", "<", 0.0),)),)).wait().count >= 0
        finally:
            _teardown(None, (), servers)

    def test_budget_spent_in_server_queue_sheds_pre_dispatch(self, data):
        # a wide batching window guarantees the 50 ms budget dies in
        # the server queue — the shed happens at dispatch pick, typed.
        # The FLUSH goes only after the window has run: a FLUSH that
        # reaches the server before the drain wakes marks the drain
        # urgent, and the drain then skips the window
        servers = tn.spawn_servers(1, flush_interval_s=0.3)
        try:
            node = RemoteNodeHandle("127.0.0.1", servers[0].port,
                                    node_id=0)
            qp = node.open_connection()
            ft = tn.schema()
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, tn.schema().encode(data))
            pend = node.submit(qp, ft, (op.Select(
                (op.Predicate("c1", "<", 0.0),)),), deadline_s=0.05)
            time.sleep(0.6)
            with pytest.raises(DeadlineExceededError, match="queue"):
                pend.wait()
            stats = node.server_stats()
            assert stats["deadline_shed"] == 1
            assert stats["queued"] == 1
            assert stats["queue_wait_s"] >= 0.05
        finally:
            _teardown(None, (), servers)

    def test_cluster_budget_decays_across_scatter_legs(self, data):
        cl = FarCluster(2, tn.CAPACITY, partitioner="hash")
        cqp = cl.open_connection()
        ct = cl.alloc_table_mem(cqp, tn.schema(), keys=data["c0"])
        cl.table_write(cqp, ct, tn.schema().encode(data))
        pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
        # a dead budget is refused before the scatter spends anything
        with pytest.raises(DeadlineExceededError):
            cl.farview_request(cqp, ct, pipe, deadline_s=0.0)
        # a tiny budget is split across legs and dies at flush pick —
        # the error is the leg's shed, re-raised (never failover-retried)
        pend = cl.submit_request(cqp, ct, pipe, deadline_s=0.001)
        time.sleep(0.05)
        with pytest.raises(DeadlineExceededError):
            pend.wait()
        # a sane budget still answers byte-identically
        res = cl.farview_request(cqp, ct, pipe, deadline_s=30.0)
        tn.assert_rows_identical(res.finalize(),
                                 tn.solo_run(pipe,
                                             tn.schema().encode(data)))


# --------------------------------------------------------------- hedging
class TestHedging:
    """A slow primary no longer sets the query's tail: the replica is
    hedged mid-flight, the first byte-identical answer wins."""

    def test_slow_primary_hedged_to_replica_in_process(self, data):
        cl = FarCluster(2, tn.CAPACITY, partitioner="hash", replicas=2,
                        slow_after_s=0.08, hedge_after_s=0.08)
        cqp = cl.open_connection()
        ct = cl.alloc_table_mem(cqp, tn.schema(), keys=data["c0"])
        words = tn.schema().encode(data)
        cl.table_write(cqp, ct, words)
        pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
        ref = tn.solo_run(pipe, words)
        # warm the jit cache first: the timing below measures the
        # HEDGE, not the first-call compile
        tn.assert_rows_identical(
            cl.farview_request(cqp, ct, pipe).finalize(), ref)
        cl.fault.slow(1, 1.2)           # stall, don't kill, node 1
        t0 = time.monotonic()
        res = cl.farview_request(cqp, ct, pipe).finalize()
        elapsed = time.monotonic() - t0
        tn.assert_rows_identical(res, ref)
        assert elapsed < 1.0, (
            f"hedge should beat the 1.2s stall, took {elapsed:.2f}s")
        # exceeding slow_after_s mid-flight is a recorded strike
        assert cl.health.state(1) != ALIVE

    def test_slow_primary_hedged_over_the_wire(self, data):
        servers = tn.spawn_servers(2)
        cl = proxies = None
        try:
            cl, proxies = chaos_cluster(
                servers, seed=5, partitioner="hash", replicas=2,
                slow_after_s=0.08, hedge_after_s=0.08)
            cqp = cl.open_connection()
            ct = cl.alloc_table_mem(cqp, tn.schema(), keys=data["c0"])
            words = tn.schema().encode(data)
            cl.table_write(cqp, ct, words)
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            ref = tn.solo_run(pipe, words)
            # warm the servers' jit caches before the timed request
            tn.assert_rows_identical(
                cl.farview_request(cqp, ct, pipe).finalize(), ref)
            # degrade ONE node's network: every frame +0.5s, both ways
            proxies[1].set_schedule(FaultSchedule(delay_s=0.5))
            t0 = time.monotonic()
            res = cl.farview_request(cqp, ct, pipe).finalize()
            elapsed = time.monotonic() - t0
            tn.assert_rows_identical(res, ref)
            assert elapsed < 3.0
            time.sleep(1.2)     # let the stalled drain finish quietly
        finally:
            _teardown(cl, proxies, servers)


# ------------------------------------------------------- circuit breaker
class TestCircuitBreaker:
    def test_lifecycle_closed_open_halfopen(self):
        b = CircuitBreaker(1, open_after=2, reset_after_s=0.05)
        assert b.state(0) == CLOSED and b.allow(0)
        b.record_failure(0)
        assert b.state(0) == CLOSED     # one strike is not an outage
        b.record_failure(0)
        assert b.state(0) == OPEN and not b.allow(0)
        time.sleep(0.06)
        assert b.allow(0)               # the single half-open probe
        assert b.state(0) == HALF_OPEN
        assert not b.allow(0)           # second caller is NOT let through
        b.record_failure(0)             # probe failed: trip again
        assert b.state(0) == OPEN
        time.sleep(0.06)
        assert b.allow(0)
        b.record_success(0)             # probe succeeded: service resumes
        assert b.state(0) == CLOSED and b.allow(0)

    def test_health_monitor_drives_the_breaker(self):
        b = CircuitBreaker(1, open_after=2, reset_after_s=60.0)
        mon = HealthMonitor(1, dead_after=3, breaker=b)
        for _ in range(2):
            mon.record_failure(0, NodeDeadError(0, op="test"))
        assert b.state(0) == OPEN
        mon.revive(0)
        assert b.state(0) == CLOSED

    def test_cluster_routes_around_open_breaker(self, data):
        cl = FarCluster(2, tn.CAPACITY, partitioner="hash", replicas=2)
        cqp = cl.open_connection()
        ct = cl.alloc_table_mem(cqp, tn.schema(), keys=data["c0"])
        words = tn.schema().encode(data)
        cl.table_write(cqp, ct, words)
        # trip node 0's breaker without marking it dead
        for _ in range(cl.breaker.open_after):
            cl.breaker.record_failure(0)
        assert cl.breaker.state(0) == OPEN
        pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
        res = cl.farview_request(cqp, ct, pipe).finalize()
        tn.assert_rows_identical(res, tn.solo_run(pipe, words))


# ------------------------------------------------- reconnect (satellite)
def _spawn_fixed_port(port: int):
    """(Re)start a server on a KNOWN port, in the harness's mode."""
    if tn.USE_SUBPROCESS:
        class _Fixed(tn._ProcServer):
            def __init__(self):     # noqa: D401 - same launch, pinned port
                cmd = [sys.executable, "-m", "repro.net.server",
                       "--port", str(port), "--node-id", "0",
                       "--capacity-mb", str(tn.CAPACITY // 2**20)]
                env = dict(os.environ)
                env["PYTHONPATH"] = (str(tn.REPO / "src") + os.pathsep
                                     + env.get("PYTHONPATH", ""))
                self.proc = subprocess.Popen(
                    cmd, stdout=subprocess.PIPE, env=env, text=True)
                deadline = time.monotonic() + 120
                while True:
                    line = self.proc.stdout.readline()
                    if line.startswith("LISTENING"):
                        self.port = int(line.split()[1])
                        break
                    if not line or time.monotonic() > deadline:
                        self.proc.kill()
                        raise RuntimeError("fixed-port server never came up")
        return _Fixed()

    class _Thread:
        def __init__(self):
            self.srv = FViewServer.start_in_thread(
                port=port, capacity_bytes=tn.CAPACITY)
            self.port = self.srv.port

        def abort(self):
            self.srv.stop_thread(abort=True)

        def stop(self):
            self.srv.stop_thread()
    return _Thread()


class TestReconnect:
    """Satellite (c): kill + restart the server on the SAME port
    mid-workload. The handle's breaker trips while it is down, then a
    single HALF_OPEN probe reconnects — byte-identical service resumes
    with NO new handle and NO cluster heal."""

    def test_handle_survives_server_restart(self, data):
        srv = tn.spawn_servers(1)[0]
        port = srv.port
        node = None
        try:
            node = RemoteNodeHandle("127.0.0.1", port, node_id=0,
                                    reconnect_attempts=2,
                                    reconnect_backoff_s=0.02,
                                    reconnect_reset_s=0.08)
            qp = node.open_connection()
            ft = tn.schema()
            words = tn.schema().encode(data)
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, words)
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            ref = tn.solo_run(pipe, words)
            tn.assert_rows_identical(
                node.submit(qp, ft, pipe).wait(), ref)

            srv.abort()                 # SIGKILL / RST: server is GONE
            srv = None
            with pytest.raises(NodeDeadError):
                node.submit(qp, ft, pipe).wait()    # transport death
            with pytest.raises(NodeDeadError):
                node.submit(qp, ft, pipe).wait()    # reconnect fails...
            # ...tripping the handle's breaker OPEN, so further verbs
            # fast-fail instead of hammering the dead port
            assert node._breaker.state(0) == OPEN
            with pytest.raises(NodeDeadError):
                node.submit(qp, ft, pipe).wait()

            srv = _spawn_fixed_port(port)   # ...and it comes back
            time.sleep(0.1)             # past the breaker reset window
            # next verb is the HALF_OPEN probe: reconnect, re-HELLO,
            # re-open the qp, and serve — the restarted node lost its
            # tables (data recovery is the CLUSTER's job), so re-ingest
            # through the SAME handle and qp, then verify byte parity
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, words)
            tn.assert_rows_identical(
                node.submit(qp, ft, pipe).wait(), ref)
            assert node._breaker.state(0) == CLOSED
        finally:
            if node is not None:
                try:
                    node.close()
                except Exception:       # noqa: BLE001
                    pass
            if srv is not None:
                srv.stop()


# ------------------------------------------------- proxy fault vocabulary
class TestChaosProxyFaults:
    """Each fault in isolation: the failure is TYPED, the recovery is
    byte-identical, and the injection sequence is seed-deterministic."""

    def _node_through_proxy(self, schedule, *, seed=0, timeout_s=60.0,
                            **server_kw):
        srv = tn.spawn_servers(1, **server_kw)[0]
        # the handle always connects CLEAN (a corrupted HELLO would just
        # fail construction); the fault plan arms after, atomically
        proxy = ChaosProxy.start_in_thread(
            "127.0.0.1", srv.port, seed=seed, schedule=CLEAN)
        node = RemoteNodeHandle("127.0.0.1", proxy.port, node_id=0,
                                timeout_s=timeout_s,
                                reconnect_backoff_s=0.02,
                                reconnect_reset_s=0.05)
        proxy.set_schedule(schedule)
        return srv, proxy, node

    def test_corruption_fails_typed_then_recovers(self, data):
        srv, proxy, node = self._node_through_proxy(CLEAN)
        try:
            qp = node.open_connection()
            ft = tn.schema()
            words = tn.schema().encode(data)
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, words)
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            ref = tn.solo_run(pipe, words)
            proxy.set_schedule(FaultSchedule(corrupt_prob=1.0))
            with pytest.raises(FarviewError):
                node.submit(qp, ft, pipe).wait()
            assert any(ev["kind"] == "corrupt" for ev in proxy.fault_log)
            proxy.set_schedule(CLEAN)
            time.sleep(0.06)            # handle breaker reset window
            # the SERVER kept the table; the handle reconnects and the
            # answer is byte-identical — zero wrong bytes throughout
            tn.assert_rows_identical(
                node.submit(qp, ft, pipe).wait(), ref)
        finally:
            node.close()
            _teardown(None, [proxy], [srv])

    def test_mid_frame_reset_reads_as_dead_node(self, data):
        srv, proxy, node = self._node_through_proxy(CLEAN)
        try:
            qp = node.open_connection()
            ft = tn.schema()
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, tn.schema().encode(data))
            # cut the connection 10 bytes into the NEXT frame
            proxy.set_schedule(FaultSchedule(reset_after_bytes=10))
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            with pytest.raises(FarviewError):
                node.submit(qp, ft, pipe).wait()
            assert any(ev["kind"] == "reset" for ev in proxy.fault_log)
        finally:
            node.close()
            _teardown(None, [proxy], [srv])

    def test_one_way_partition_reads_as_dead_node(self, data):
        srv, proxy, node = self._node_through_proxy(CLEAN, timeout_s=1.0)
        try:
            qp = node.open_connection()
            ft = tn.schema()
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, tn.schema().encode(data))
            proxy.set_schedule(FaultSchedule(partition_s2c=True))
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            t0 = time.monotonic()
            with pytest.raises(NodeDeadError):
                node.submit(qp, ft, pipe).wait()
            # the client timeout bounded the stall: no infinite hang
            assert time.monotonic() - t0 < 30.0
            assert any(ev["kind"] == "partition"
                       for ev in proxy.fault_log)
        finally:
            node.close()
            _teardown(None, [proxy], [srv])

    def test_duplicate_frames_are_exactly_once(self, data):
        srv, proxy, node = self._node_through_proxy(
            FaultSchedule(duplicate_prob=1.0))
        try:
            qp = node.open_connection()
            ft = tn.schema()
            words = tn.schema().encode(data)
            node.pool.alloc_table(ft)
            node.pool.write_table(ft, words)
            pipe = (op.Select((op.Predicate("c1", "<", 0.0),)),)
            ref = tn.solo_run(pipe, words)
            # every frame delivered twice; req-id correlation absorbs
            # the echoes and the answer is still byte-identical
            tn.assert_rows_identical(
                node.submit(qp, ft, pipe).wait(), ref)
            assert any(ev["kind"] == "duplicate"
                       for ev in proxy.fault_log)
        finally:
            node.close()
            _teardown(None, [proxy], [srv])

    def test_same_seed_same_fault_sequence(self, data):
        def one_run(seed):
            srv, proxy, node = self._node_through_proxy(
                FaultSchedule(corrupt_prob=0.5, duplicate_prob=0.5),
                seed=seed, timeout_s=1.0)
            try:
                ft = tn.schema()
                try:
                    node.open_connection()
                    node.pool.alloc_table(ft)
                    node.pool.write_table(ft, tn.schema().encode(data))
                except FarviewError:
                    pass                # corruption may kill the conn
                return [(ev["kind"], ev["detail"])
                        for ev in proxy.fault_log]
            finally:
                node.close()
                _teardown(None, [proxy], [srv])

        log_a, log_b = one_run(42), one_run(42)
        assert log_a == log_b and log_a, (
            "seeded chaos must replay identically")
