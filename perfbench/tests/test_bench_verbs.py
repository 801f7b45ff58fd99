"""The verbs `rows` and `group` on fixed small inputs: the reference
answer, the comparison, and the least bytes a query moves and its answer
carries. The expected values were read from the functions before they
moved into the verbs' files, so the move changed no reading."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from fvb import spec as fspec  # noqa: E402

ROWS = fspec.module(BENCH / "verbs" / "rows.py")
GROUP = fspec.module(BENCH / "verbs" / "group.py")

# dyadic values: exact in float32, bfloat16 and float64
WORDS = np.array([[3, 0.125, 0.875, 5],
                  [1, 0.625, 0.25, 2],
                  [3, 0.375, 0.5, 7],
                  [2, 0.75, 0.625, 1],
                  [1, 0.25, 0.5, 4],
                  [2, 0.5, 0.125, 3]], np.float32)
NAMES = ["k", "a", "b", "c"]
index = NAMES.index
PROJ = {"verb": "rows", "project": ["k", "b"], "select": [["a", "<", 0.5]]}
SMART = {"verb": "rows", "smart": ["b", "k"],
         "select": [["a", ">=", 0.375], ["c", "<", 7]]}
WHOLE = {"verb": "rows", "select": [["b", ">", 0.45]]}
GROUPED = {"verb": "group", "select": [["a", "<", 0.7]],
           "group": {"key": "k", "values": ["c", "a"],
                     "aggs": ["count", "sum", "min", "max"], "n_buckets": 8}}


def _inexact():
    w = WORDS.copy()
    w[:, 1] += np.float32(1 / 3)
    w[:, 2] += np.float32(1 / 7)
    w[:, 3] = w[:, 3] * 1001 + 3
    return w


@pytest.mark.parametrize("spec,count,out_cols,narrowed,rows", [
    (PROJ, 3, [0, 2], False, [[3, 0.875], [3, 0.5], [1, 0.5]]),
    (SMART, 3, [2, 0], True, [[0.25, 1], [0.625, 2], [0.125, 2]]),
    (WHOLE, 4, [0, 1, 2, 3], False, [[3, 0.125, 0.875, 5],
                                     [3, 0.375, 0.5, 7],
                                     [2, 0.75, 0.625, 1],
                                     [1, 0.25, 0.5, 4]]),
])
def test_rows_expect(spec, count, out_cols, narrowed, rows):
    want = ROWS.expect(WORDS, index, spec)
    assert want.count == count
    assert want.out_cols.tolist() == out_cols
    assert want.narrowed is narrowed and want.width == 4
    assert want.rows.tolist() == rows


def _full(want):
    rows = np.zeros((6, 4), np.float32)
    rows[: want.count][:, want.out_cols] = want.rows
    return rows


def _leak(rows):
    rows[0, 1] = 9.0
    return rows


def _word(rows):
    rows[1, 0] += 1
    return rows


@pytest.mark.parametrize("spec,make,got", [
    (PROJ, lambda w: (w.count, _full(w)), (0, 0)),
    (PROJ, lambda w: (w.count, w.rows.copy()), (0, 0)),
    (PROJ, lambda w: (w.count, _leak(_full(w))), (0, 1)),
    (PROJ, lambda w: (w.count, _word(_full(w))), (0, 1)),
    (PROJ, lambda w: (w.count - 1, _full(w)), (1, 2)),
    (PROJ, lambda w: (w.count + 1, _full(w)), (1, 2)),
    (PROJ, lambda w: (w.count, _full(w)[:, :3]), (0, 6)),
    (SMART, lambda w: (w.count, w.rows.copy()), (0, 0)),
    (SMART, lambda w: (w.count, _full(w)), (0, 6)),
    (WHOLE, lambda w: (w.count, WORDS[WORDS[:, 2] > 0.45]), (0, 0)),
])
def test_rows_compare(spec, make, got):
    want = ROWS.expect(WORDS, index, spec)
    assert ROWS.compare(make(want), want) == {"bad_count": got[0],
                                              "bad_words": got[1]}


@pytest.mark.parametrize("spec,count,bad_words", [
    (PROJ, 1, 1), (SMART, 0, 0), (WHOLE, 4, 11)])
def test_rows_control(spec, count, bad_words):
    words = _inexact()
    spec = json.loads(json.dumps(spec))
    want = ROWS.expect(words, index, spec)
    assert want.count == count
    assert ROWS.compare(ROWS.control(words, index, spec), want) == \
        {"bad_count": 0, "bad_words": bad_words}
    # on words bfloat16 holds exactly the control is the reference
    exact = ROWS.expect(WORDS, index, spec)
    assert ROWS.compare(ROWS.control(WORDS, index, spec), exact) == \
        {"bad_count": 0, "bad_words": 0}


def test_group_expect():
    want = GROUP.expect(WORDS, index, GROUPED)
    assert want.aggs == ("count", "sum", "min", "max")
    got = {k: (c, s.tolist(), mn.tolist(), mx.tolist())
           for k, (c, s, mn, mx) in want.groups.items()}
    assert got == {1: (2, [6.0, 0.875], [2.0, 0.25], [4.0, 0.625]),
                   2: (1, [3.0, 0.5], [3.0, 0.5], [3.0, 0.5]),
                   3: (2, [12.0, 0.5], [5.0, 0.125], [7.0, 0.375])}


def _sum_off(groups):
    groups[1][1] = groups[1][1] + np.array([1.0, 0.0])
    return groups


def _missing_and_extra(groups):
    groups[99] = groups.pop(1)
    return groups


@pytest.mark.parametrize("alter,bad", [
    (lambda g: g, 0), (_sum_off, 1), (_missing_and_extra, 2)])
def test_group_compare(alter, bad):
    want = GROUP.expect(WORDS, index, GROUPED)
    answer = alter({k: list(v) for k, v in want.groups.items()})
    assert GROUP.compare(answer, want) == {"bad_groups": bad}


def test_group_control():
    words = _inexact()
    spec = json.loads(json.dumps(GROUPED).replace("0.7", "1.1"))
    want = GROUP.expect(words, index, spec)
    assert GROUP.compare(GROUP.control(words, index, spec), want) == \
        {"bad_groups": 3}
    exact = GROUP.expect(WORDS, index, GROUPED)
    assert GROUP.compare(GROUP.control(WORDS, index, GROUPED), exact) == \
        {"bad_groups": 0}


@pytest.mark.parametrize("verb,spec,count,query,answer", [
    # 6 rows of 4 words, 4-byte words
    (ROWS, PROJ, 0, 72, 0), (ROWS, PROJ, 3, 96, 24),
    (ROWS, SMART, 0, 96, 0), (ROWS, SMART, 3, 120, 24),
    (ROWS, WHOLE, 0, 96, 0), (ROWS, WHOLE, 3, 144, 48),
    # a group's count is its overflow rows
    (GROUP, GROUPED, 0, 328, 0), (GROUP, GROUPED, 3, 364, 36),
])
def test_bytes(verb, spec, count, query, answer):
    assert verb.query_bytes(spec, 4, 6, 4, count) == query
    assert verb.answer_bytes(spec, 4, 4, count) == answer


@pytest.mark.parametrize("verb", [ROWS, GROUP])
def test_verb_has_what_the_harness_reads(verb):
    for name in ("pipeline", "answer", "expect", "compare", "control",
                 "query_bytes", "answer_bytes"):
        assert callable(getattr(verb, name))
    assert verb.KEEP is None or verb.KEEP >= 1
    assert verb.LIMITS and all(v == 0 for v in verb.LIMITS.values())
