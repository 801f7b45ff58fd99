"""The fold of the program's own spans and device scopes (fvb/layers.py):
checked by hand on a synthetic trace, against `ProfileData` on the
committed trace, on a rehearsal of the served path traced on the CPU,
and on a small trace recorded on one TPU v5e whose ops carry the scopes."""
import gzip
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from fvb import harness  # noqa: E402
from fvb import layers  # noqa: E402
from fvb import tracefold as tf  # noqa: E402

MS = 1e6        # ns
DATA = Path(__file__).with_name("data")
HOST_SPANS = ("srv.encode", "fv.recv", "fv.crc", "fv.decode", "fv.attach")


def test_spans_clip_to_the_window_and_sum_over_threads():
    # window 10..100 ms; two threads' fv.recv overlap (both count), one
    # span starts before the window, one runs past it, one lies outside
    spans = [tf.Event(tf.WINDOW_SPAN, 10 * MS, 90 * MS),
             tf.Event("fv.recv", 20 * MS, 10 * MS),
             tf.Event("fv.recv", 25 * MS, 10 * MS),
             tf.Event("srv.encode", 0, 15 * MS),
             tf.Event("fv.d2h", 95 * MS, 20 * MS),
             tf.Event("fv.crc", 120 * MS, 5 * MS)]
    got = layers.span_seconds(spans, 10 * MS, 100 * MS)
    assert got == {"fv.recv": pytest.approx(0.020),
                   "srv.encode": pytest.approx(0.005),
                   "fv.d2h": pytest.approx(0.005), "fv.crc": 0.0}


def test_a_scope_counts_nested_ops_once():
    # a while (10..40) over its body's fusions (12..20, 22..30), a gather
    # under the scope in a vmap (45..50), an op under another scope and
    # one under a name that only starts like the scope; window 0..48
    ops = {"/device:TPU:0": [
        (10 * MS, 40 * MS, "jit(f)/fv.stitch/jit(searchsorted)/while"),
        (12 * MS, 20 * MS, "jit(f)/fv.stitch/jit(searchsorted)/while/body/"
                           "gather:"),
        (22 * MS, 30 * MS, "jit(f)/fv.stitch/while/body/select_n:"),
        (45 * MS, 50 * MS, "jit(f)/vmap(fv.stitch)/gather:"),
        (40 * MS, 44 * MS, "jit(f)/fv.ovf_pack/sort:"),
        (0, 10 * MS, "jit(f)/fv.stitchy/add:")]}
    assert layers.scope_seconds(ops, ("fv.stitch",), 0, 48 * MS) == \
        pytest.approx(0.030 + 0.003)
    assert layers.scope_seconds(ops, ("fv.stitch", "fv.ovf_pack"), 0,
                                48 * MS) == pytest.approx(0.037)
    assert layers.scope_seconds(ops, ("fv.bucket_sort",), 0, 48 * MS) == 0


@pytest.fixture(scope="module")
def old_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("old") / "small.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "small_select.xplane.pb.gz").read_bytes()))
    return path


def test_device_ops_read_as_profile_data_reads_them(old_trace):
    """The XSpace reader finds the same op events, at the same times, as
    `ProfileData` (which rounds to whole nanoseconds), with name paths."""
    mine = layers.scoped_ops(old_trace)
    theirs = tf.load(old_trace).ops
    assert list(mine) == list(theirs) == ["/device:TPU:0"]
    a, b = mine["/device:TPU:0"], theirs["/device:TPU:0"]
    assert len(a) == len(b) == 1572
    assert all(abs(s - e.start) < 1 and abs((t - s) - e.dur) < 1
               for (s, t, _), e in zip(a, b))
    paths = [p for _, _, p in a]
    assert sum(1 for p in paths if "jit(searchsorted)" in p) == 240
    # a trace recorded before the scopes existed: nothing under them
    assert layers.fold(old_trace)["scope_s"] == {
        "fv.bucket_sort": 0.0, "fv.ovf_pack": 0.0, "fv.stitch": 0.0}


def test_traced_rehearsal_shows_every_wire_span_per_select(tmp_path,
                                                           capsys):
    tdir = tmp_path / "trace"
    rc = harness.main(["--workload", "fv64.select", "--seed", "9",
                       "--seconds", "0.3", "--trace", "1", "--rehearse",
                       "--trace-dir", str(tdir)])
    assert rc == 0
    capsys.readouterr()
    tr = tf.load(tdir)
    win = next(s for s in tr.spans if s.name == tf.WINDOW_SPAN)
    queries = [s for s in tr.spans if s.name == "fv.finalize"
               and win.start <= s.start < win.end]
    assert queries
    for name in HOST_SPANS:
        inside = [s for s in tr.spans if s.name == name
                  and win.start <= s.start < win.end]
        assert len(inside) >= len(queries), name
        assert min(s.dur for s in inside) > 0, name
    f = layers.fold(tdir)
    assert f["queries"] == len(queries)
    for name in HOST_SPANS:
        assert f["span_s"][name] > 0, name
    assert {"encode_ms_per_q", "socket_ms_per_q",
            "decode_ms_per_q"} <= set(f["per_query_ms"])
    # the CPU lowering lays no column-major answer out, and its trace has
    # no device plane
    assert "stitch_ms_per_q" not in f["per_query_ms"]


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """One S25P select and one G50 group-by at 4096 rows, traced on one
    TPU v5e after the program's spans and scopes were added; kept to the
    device's module and op lines (each op's metadata to its `tf_op` stat)
    and the host's `fv.`/`srv.`/`bench.` spans inside the window."""
    path = tmp_path_factory.mktemp("chip") / "scoped.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "small_scoped.xplane.pb.gz").read_bytes()))
    return path


def test_recorded_chip_trace_folds_the_scopes(chip_trace):
    f = layers.fold(chip_trace)
    assert f["queries"] == 2 and f["op_paths"]
    ops = layers.scoped_ops(chip_trace)["/device:TPU:0"]
    assert len(ops) == 800
    # by hand: the ops whose name path holds the scope; they do not
    # overlap here (a `while` op carries no path, its body's ops do)
    for scope, n, secs in (("fv.stitch", 35, 0.000513252),
                           ("fv.bucket_sort", 152, 0.000388203),
                           ("fv.ovf_pack", 8, 0.000129776)):
        evs = [(s, e) for s, e, p in ops if f"/{scope}/" in p]
        assert len(evs) == n, scope
        assert f["scope_s"][scope] == pytest.approx(
            sum(e - s for s, e in evs) / 1e9) == pytest.approx(secs)
    # every scoped op runs inside one of the executables' module events
    mods = [(e.start, e.end) for e in tf.load(chip_trace).modules[
        "/device:TPU:0"] if "_pages_entry" in e.name]
    assert len(mods) == 2
    assert all(any(a <= s and e <= b + 1 for a, b in mods)
               for s, e, p in ops if "/fv." in p)
    assert set(f["per_query_ms"]) == set(layers.LAYERS)
    assert f["per_query_ms"]["group_sort_ms_per_q"] == pytest.approx(
        (0.000388203 + 0.000129776) / 2 * 1e3)


def test_recorded_chip_trace_has_every_host_span(chip_trace):
    """The select's answer is copied off and laid out, the group's
    overflow copied off; each query's RESULT and FLUSH acknowledgement
    are encoded, read, checked and decoded; each result is attached."""
    names = [s.name for s in tf.load(chip_trace).spans]
    assert names.count("fv.d2h") == 2 and names.count("fv.layout") == 1
    for name in ("srv.encode", "fv.recv", "fv.crc", "fv.decode"):
        assert names.count(name) == 4, name
    assert names.count("fv.attach") == 2
    spans = layers.fold(chip_trace)["span_s"]
    assert all(spans[n] > 0 for n in ("fv.d2h", "fv.layout") + HOST_SPANS)
