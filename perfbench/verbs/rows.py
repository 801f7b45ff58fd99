"""The verb `rows`: selection and projection, answered with the surviving
rows.

An instance:

    {"name": "S25P", "verb": "rows",
     "project": [cols] | "smart": [cols],       (at most one of the two)
     "select": [[col, op, value], ...]}         (AND of predicates)

The answer is the survivor count and the rows, in table order; rows past
the count are not compared. A Project answer may come back full width
with the other columns zero, or narrowed to the projected columns; a
SmartAddress answer is narrowed. Two answers of each instance, drawn from
the seed, are kept for the check.

The numbers compared, each held to the limit 0: `bad_count` (survivor
count off the reference's) and `bad_words` (survivor words that differ
bit-wise from the reference's, words of missing or extra rows, and
non-zero words outside a projection).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from fvb.reference import mask, to_bf16

KEEP = 2
LIMITS = {"bad_count": 0, "bad_words": 0}


def pipeline(spec: dict, config: dict) -> list:
    from repro.core import operators as op
    ops = []
    if "project" in spec:
        ops.append(op.Project(tuple(spec["project"])))
    if "smart" in spec:
        ops.append(op.SmartAddress(tuple(spec["smart"])))
    if spec.get("select"):
        ops.append(op.Select(tuple(op.Predicate(c, o, float(v))
                                   for c, o, v in spec["select"])))
    return ops


def answer(res, ft, pipeline: tuple) -> tuple:
    """((count, rows), count) of a finalized result."""
    count = int(res.count)
    return (count, res.rows), count


@dataclass
class Expected:
    count: int
    out_cols: np.ndarray            # the answer's columns, table order
    narrowed: bool                  # SmartAddress: the answer is narrowed
    width: int                      # the table's words per row
    rows: np.ndarray                # (count, len(out_cols)) survivors


def _out_cols(n_cols: int, index, spec: dict) -> np.ndarray:
    cols = spec.get("smart") or spec.get("project")
    if cols is None:
        return np.arange(n_cols)
    return np.asarray([index(c) for c in cols])


def expect(words: np.ndarray, index, spec: dict) -> Expected:
    """The reference answer to the instance `spec` over `words`."""
    out = _out_cols(words.shape[1], index, spec)
    rows = words[mask(words, index, spec)]
    if len(out) != words.shape[1]:
        rows = rows[:, out]
    return Expected(count=int(rows.shape[0]), out_cols=out,
                    narrowed="smart" in spec, width=words.shape[1],
                    rows=rows)


def compare(answer, want: Expected) -> dict:
    """The numbers compared, each to be held to LIMITS. `answer` is what
    the client holds: (count, rows)."""
    count, rows = answer
    count = int(count)
    rows = np.asarray(rows)
    k = len(want.out_cols)
    bad_count = abs(count - want.count)
    whole = {"bad_count": bad_count, "bad_words": max(count, want.count) * k}
    if rows.ndim != 2 or rows.shape[0] < count:
        return whole
    surv = rows[:count].astype(np.float32, copy=False)
    leaked = 0
    if want.narrowed or (rows.shape[1] == k and k != want.width):
        if rows.shape[1] != k:
            return whole
        got = surv
    elif rows.shape[1] == want.width:
        got = surv[:, want.out_cols]
        other = np.setdiff1d(np.arange(want.width), want.out_cols)
        if other.size:
            leaked = int(np.count_nonzero(
                np.ascontiguousarray(surv[:, other]).view(np.uint32)))
    else:
        return whole
    n = min(count, want.count)
    off = np.count_nonzero(
        np.ascontiguousarray(got[:n]).view(np.uint32)
        != np.ascontiguousarray(want.rows[:n]).view(np.uint32))
    return {"bad_count": bad_count,
            "bad_words": int(off) + bad_count * k + leaked}


def control(words: np.ndarray, index, spec: dict):
    """The reference in bfloat16, in the client's answer format."""
    want = expect(to_bf16(words), index, spec)
    if want.narrowed:
        return want.count, want.rows
    rows = np.zeros((words.shape[0], words.shape[1]), np.float32)
    rows[: want.count][:, want.out_cols] = want.rows
    return want.count, rows


def query_bytes(spec: dict, word_bytes: int, n_rows: int, width: int,
                count: int) -> int:
    """The HBM bytes the query's semantics require, whatever lowering runs
    it: every row's referenced words read once, plus every survivor's
    output words written once. A pre-decrypt reads the same words: the CTR
    keystream is positional, so any word decrypts alone. `count` is the
    survivor count."""
    sel = {c for c, _, _ in spec.get("select", ())}
    cols = spec.get("smart") or spec.get("project")
    k = width if cols is None else len(cols)
    ref = width if cols is None else len(sel | set(cols))
    return (n_rows * ref + count * k) * word_bytes


def answer_bytes(spec: dict, word_bytes: int, width: int,
                 count: int) -> int:
    """The least bytes an answer carries, however it is framed: every
    survivor's output words. `count` is the survivor count."""
    cols = spec.get("smart") or spec.get("project")
    return count * (width if cols is None else len(cols)) * word_bytes
