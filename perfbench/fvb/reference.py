"""The plain reference and the comparison that decides `correct`.

Numpy over the plaintext words the benchmark made; nothing of the program
is imported and nothing it made is read. Semantics:

  * select / project: the rows where every predicate holds (f32 value
    against the f32 constant), in table order. The answer's first `count`
    rows are the survivors; a Project answer may come back full width with
    the other columns zero, or narrowed to the projected columns; a
    SmartAddress answer is narrowed. Rows past `count` are not compared.
  * group-by: per key of the selected rows, the count and the sum, min and
    max of each value column, exact (the values are small integers).

Each comparison gives plain counts, each held to the limit 0:
`bad_count` (survivor count off the reference's), `bad_words` (survivor
words that differ bit-wise from the reference's, words of missing or extra
rows, and non-zero words outside a projection), `bad_groups` (keys missing,
extra, or with any aggregate off).

The control is this reference computed in bfloat16, the precision below
the configuration's float32 words: the words rounded to bfloat16 before the
predicates and in the answer, and each aggregate rounded to bfloat16 (the
best a bfloat16 accumulation could give). It must fail.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
       ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}
LIMITS = {"bad_count": 0, "bad_words": 0, "bad_groups": 0}


def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


@dataclass
class Expected:
    kind: str                       # "rows" | "groups"
    count: int = 0
    out_cols: np.ndarray | None = None  # the answer's columns, table order
    narrowed: bool = False          # SmartAddress: the answer is narrowed
    width: int = 0                  # the table's words per row
    rows: np.ndarray | None = None  # (count, len(out_cols)) survivors
    groups: dict | None = None      # key -> (count, sums, mins, maxs)
    aggs: tuple = ()


def _mask(words: np.ndarray, index, spec: dict) -> np.ndarray:
    m = np.ones(words.shape[0], bool)
    for col, op, value in spec.get("select", ()):
        m &= OPS[op](words[:, index(col)], np.float32(value))
    return m


def _out_cols(n_cols: int, index, spec: dict) -> np.ndarray:
    cols = spec.get("smart") or spec.get("project")
    if cols is None:
        return np.arange(n_cols)
    return np.asarray([index(c) for c in cols])


def expect(words: np.ndarray, index, spec: dict) -> Expected:
    """The reference answer to the instance `spec` over `words`."""
    mask = _mask(words, index, spec)
    if "group" in spec:
        g = spec["group"]
        keys = words[mask, index(g["key"])].astype(np.int64)
        vals = words[np.ix_(mask, [index(c) for c in g["values"]])]
        return Expected("groups", groups=group_totals(keys, vals),
                        aggs=tuple(g["aggs"]))
    out = _out_cols(words.shape[1], index, spec)
    rows = words[mask]
    if len(out) != words.shape[1]:
        rows = rows[:, out]
    return Expected("rows", count=int(rows.shape[0]), out_cols=out,
                    narrowed="smart" in spec, width=words.shape[1],
                    rows=rows)


def group_totals(keys: np.ndarray, vals: np.ndarray) -> dict:
    """{key: (count, sums, mins, maxs)} by sorting, in float64."""
    if keys.size == 0:
        return {}
    order = np.argsort(keys, kind="stable")
    k, v = keys[order], vals[order].astype(np.float64)
    start = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    return {int(key): (int(c), s, mn, mx) for key, c, s, mn, mx in zip(
        k[start], np.diff(np.r_[start, len(k)]),
        np.add.reduceat(v, start), np.minimum.reduceat(v, start),
        np.maximum.reduceat(v, start))}


def compare(answer, want: Expected) -> dict:
    """The numbers compared, each to be held to LIMITS. `answer` is what
    the client holds: (count, rows) for rows, {key: [count, sum, min,
    max]} for groups."""
    if want.kind == "groups":
        return {"bad_groups": _bad_groups(answer, want)}
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


def _bad_groups(got: dict, want: Expected) -> int:
    idx = {"count": 0, "sum": 1, "min": 2, "max": 3}
    bad = len(set(got) ^ set(want.groups))
    for key in set(got) & set(want.groups):
        g, w = got[key], want.groups[key]
        for agg in want.aggs:
            i = idx[agg]
            if not np.array_equal(np.asarray(g[i], np.float64),
                                  np.asarray(w[i], np.float64)):
                bad += 1
                break
    return bad


# ----------------------------------------------------------- the control
def control_answer(words: np.ndarray, index, spec: dict):
    """The reference in bfloat16, in the client's answer format."""
    low = to_bf16(words)
    if "group" in spec:
        want = expect(low, index, spec)
        return {k: [int(to_bf16(np.float32([c]))[0]), to_bf16(s),
                    to_bf16(mn), to_bf16(mx)]
                for k, (c, s, mn, mx) in want.groups.items()}
    want = expect(low, index, spec)
    if want.narrowed:
        return want.count, want.rows
    rows = np.zeros((words.shape[0], words.shape[1]), np.float32)
    rows[: want.count][:, want.out_cols] = want.rows
    return want.count, rows
