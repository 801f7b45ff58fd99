"""The verb `group`: a hash group-by, answered with per-key aggregates
after the client's merge of the overflow rows.

An instance:

    {"name": "G50", "verb": "group",
     "select": [[col, op, value], ...],         (AND of predicates)
     "group": {"key": col, "values": [cols], "aggs": [...], "n_buckets": n}}

The answer is {key: [count, sums, mins, maxs]} over the selected rows,
merged by the client (`merge_group_partials`, under the span `fv.merge`);
its count is the overflow rows the node shipped. Every answer is kept for
the check. The reference is exact: per key of the selected rows, the
count and the sum, min and max of each value column, in float64 (the
values are small integers).

The number compared, held to the limit 0: `bad_groups` (keys missing,
extra, or with any aggregate off).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from fvb.reference import mask, to_bf16

KEEP = None
LIMITS = {"bad_groups": 0}
_AGG = {"count": 0, "sum": 1, "min": 2, "max": 3}


def pipeline(spec: dict, config: dict) -> list:
    from repro.core import operators as op
    ops = []
    if spec.get("select"):
        ops.append(op.Select(tuple(op.Predicate(c, o, float(v))
                                   for c, o, v in spec["select"])))
    g = spec["group"]
    ops.append(op.GroupBy(g["key"], tuple(g["values"]),
                          aggs=tuple(g["aggs"]),
                          n_buckets=int(g["n_buckets"])))
    return ops


def answer(res, ft, pipeline: tuple) -> tuple:
    """(merged groups, overflow rows) of a finalized result."""
    from jax.profiler import TraceAnnotation

    from repro.core import client as fv
    count = len(res.groups["ovf_keys"])
    with TraceAnnotation("fv.merge"):
        groups = fv.merge_group_partials(ft, pipeline, [res]).groups
    return groups, count


@dataclass
class Expected:
    groups: dict                    # key -> (count, sums, mins, maxs)
    aggs: tuple


def expect(words: np.ndarray, index, spec: dict) -> Expected:
    """The reference answer to the instance `spec` over `words`."""
    m = mask(words, index, spec)
    g = spec["group"]
    keys = words[m, index(g["key"])].astype(np.int64)
    vals = words[np.ix_(m, [index(c) for c in g["values"]])]
    return Expected(groups=group_totals(keys, vals), aggs=tuple(g["aggs"]))


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


def compare(answer: dict, want: Expected) -> dict:
    """The number compared, to be held to LIMITS. `answer` is what the
    client holds: {key: [count, sum, min, max]}."""
    bad = len(set(answer) ^ set(want.groups))
    for key in set(answer) & set(want.groups):
        g, w = answer[key], want.groups[key]
        for agg in want.aggs:
            i = _AGG[agg]
            if not np.array_equal(np.asarray(g[i], np.float64),
                                  np.asarray(w[i], np.float64)):
                bad += 1
                break
    return {"bad_groups": bad}


def control(words: np.ndarray, index, spec: dict) -> dict:
    """The reference in bfloat16, in the client's answer format."""
    want = expect(to_bf16(words), index, spec)
    return {k: [int(to_bf16(np.float32([c]))[0]), to_bf16(s),
                to_bf16(mn), to_bf16(mx)]
            for k, (c, s, mn, mx) in want.groups.items()}


def query_bytes(spec: dict, word_bytes: int, n_rows: int, width: int,
                count: int) -> int:
    """The HBM bytes the query's semantics require, whatever lowering runs
    it: every row's referenced words read once, the bucket table (per
    bucket: key, count, and sum, min and max of each value) and the
    overflow rows (key and values) written once. A pre-decrypt reads the
    same words: the CTR keystream is positional, so any word decrypts
    alone. `count` is the overflow rows."""
    sel = {c for c, _, _ in spec.get("select", ())}
    g = spec["group"]
    ref = sel | {g["key"]} | set(g["values"])
    nv = len(g["values"])
    out = g["n_buckets"] * (2 + 3 * nv) + count * (1 + nv)
    return (n_rows * len(ref) + out) * word_bytes


def answer_bytes(spec: dict, word_bytes: int, width: int,
                 count: int) -> int:
    """The least bytes an answer carries, however it is framed: the
    overflow rows (key and values), which the client merges itself; the
    buckets may ship compacted. `count` is the overflow rows."""
    return count * (1 + len(spec["group"]["values"])) * word_bytes
