"""The reduction from a profiler trace to the per-layer numbers: device
busy union, idle gaps labelled by the benchmark's host spans, executable
and kernel time. Checked by hand on a synthetic trace, and on a small
trace recorded on one TPU v5e and committed beside this file."""
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from fvb import tracefold as tf  # noqa: E402

MS = 1e6        # ns


def _synthetic() -> tf.Trace:
    # window 0..100 ms; one chip: module A (exec) 10..40 holding ops
    # 10..20 (kernel), 15..30 (nested, overlaps) and 32..40; module B
    # (the merge) 60..70 with one op; op 95..120 runs past the window
    ops = [tf.Event("%k.1 = f32[8] custom-call(), custom_call_target="
                    '"tpu_custom_call"', 10 * MS, 10 * MS),
           tf.Event("fusion.2", 15 * MS, 15 * MS),
           tf.Event("copy.3", 32 * MS, 8 * MS),
           tf.Event("sort.4", 60 * MS, 10 * MS),
           tf.Event("copy.5", 95 * MS, 25 * MS)]
    mods = [tf.Event("jit__pages_entry(7)", 10 * MS, 30 * MS),
            tf.Event("jit__segment_merge_groups(9)", 60 * MS, 10 * MS)]
    spans = [tf.Event(tf.WINDOW_SPAN, 0, 100 * MS),
             tf.Event("fv.wait", 0, 50 * MS),
             tf.Event("srv.batch", 38 * MS, 12 * MS),
             tf.Event("fv.merge", 55 * MS, 45 * MS)]
    return tf.Trace(ops={"/device:TPU:0": ops},
                    modules={"/device:TPU:0": mods}, spans=spans)


def test_union_and_gaps_by_hand():
    assert tf.union([(5, 9), (1, 3), (2, 4), (9, 10), (12, 12)]) == \
        [[1, 4], [5, 10]]
    assert tf.gaps_of([[1, 4], [5, 10]], 0, 12) == [[0, 1], [4, 5], [10, 12]]
    assert tf.clip([[1, 4], [5, 10]], 2, 6) == [[2, 4], [5, 6]]


def test_fold_by_hand():
    f = tf.fold(_synthetic(), [re.compile("tpu_custom_call")])
    assert f.n_chips == 1
    assert f.window_s == pytest.approx(0.100)
    # busy: 10..30, 32..40, 60..70, 95..100 = 20 + 8 + 10 + 5 ms
    assert f.busy_s == pytest.approx(0.043)
    assert f.exec_s == pytest.approx(0.030)         # module A only
    assert f.kernel_s == pytest.approx(0.010)       # the custom call only
    # gaps: 0..10 (fv.wait), 40..60 (srv.batch covers 40..50, fv.merge
    # 55..60, fv.wait 40..50: ties go to the shorter span), 70..95
    # (fv.merge), 30..32 (fv.wait)
    assert f.gaps == [("fv.merge", pytest.approx(0.025)),
                      ("srv.batch", pytest.approx(0.020)),
                      ("fv.wait", pytest.approx(0.010)),
                      ("fv.wait", pytest.approx(0.002))]
    assert f.top_ops[0] == ("fusion.2", pytest.approx(0.015))


def test_no_device_plane_folds_to_nothing():
    f = tf.fold(tf.Trace(spans=[tf.Event(tf.WINDOW_SPAN, 0, 10 * MS)]), [])
    assert f.n_chips == 0 and f.busy_s == 0.0 and f.gaps == []


RECORDED = Path(__file__).with_name("data") / "small_select.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A traced rehearsal of fv64.select (4096 rows, a 0.2 s window) on one
    TPU v5e, kept to the device's module and op lines and the benchmark's
    own host spans."""
    import gzip
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return path


def test_recorded_trace_folds(recorded):
    tr = tf.load(recorded)
    assert list(tr.ops) == ["/device:TPU:0"]
    assert len(tr.ops["/device:TPU:0"]) == 1572
    assert len(tr.modules["/device:TPU:0"]) == 96
    names = {s.name for s in tr.spans}
    assert {tf.WINDOW_SPAN, "fv.send", "fv.wait", "fv.finalize",
            "srv.batch", "srv.dispatch", "srv.send"} <= names
    f = tf.fold(tr)
    assert f.n_chips == 1
    assert f.window_s == pytest.approx(0.212741705)
    assert f.busy_s == pytest.approx(0.014285595)
    assert 0 < f.busy_s < f.window_s
    assert f.exec_s == pytest.approx(0.014316368)
    # by hand: the select kernel's custom calls, summed over the window
    win = next(s for s in tr.spans if s.name == tf.WINDOW_SPAN)
    kern = sum(e.dur for e in tr.ops["/device:TPU:0"]
               if e.name.startswith("%select_project")
               and "tpu_custom_call" in e.name
               and win.start <= e.start and e.end <= win.end)
    assert f.kernel_s == pytest.approx(kern / 1e9) == \
        pytest.approx(0.000349731)
    assert len(f.gaps) == 10 and all(g[0] != "none" for g in f.gaps)
    assert f.top_ops[0] == ("%copy-done copy-done",
                            pytest.approx(0.001099195))
