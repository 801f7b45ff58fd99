"""The verb `aggregate`: a scan, a filter and an exact scalar aggregate
pushed down whole (TPC-H Q6), answered with the count and the sums.

An instance:

    {"name": "Q6_1994", "verb": "aggregate",
     "select": [[col, op, value], ...],         (AND of predicates; several
                                                 may bound one column)
     "sums": [[col] | [col_a, col_b], ...]}     (sum of a column or of the
                                                 product of two)

The answer is (count, sums): the qualifying rows and each sum as an exact
int. Every answer is kept for the check. The reference is numpy int64
over the plaintext words: the f32 predicates of `fvb.reference.mask`,
then each term's product and sum.

The numbers compared, each held to the limit 0: `bad_count` (the count
off the reference's) and `bad_sums` (sums not equal to the reference's).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from fvb.reference import mask, to_bf16

KEEP = None
LIMITS = {"bad_count": 0, "bad_sums": 0}
ANSWER_WORD_BYTES = 8               # the count and each sum: 8 bytes


def pipeline(spec: dict, config: dict) -> list:
    from repro.core import operators as op
    return [op.Select(tuple(op.Predicate(c, o, float(v))
                            for c, o, v in spec["select"])),
            op.Aggregate(tuple(tuple(t) for t in spec["sums"]))]


def answer(res, ft, pipeline: tuple) -> tuple:
    """((count, sums), count) of a finalized result."""
    s = res.scalars
    count = int(s["count"])
    return (count, tuple(int(x) for x in s["sums"])), count


@dataclass
class Expected:
    count: int
    sums: tuple                     # one exact int per term

    # It reads as a narrowed answer of the verb `rows`, (count, rows),
    # whose rows are the sums: the form the client holds.
    narrowed = True

    @property
    def rows(self) -> tuple:
        return self.sums


def expect(words: np.ndarray, index, spec: dict) -> Expected:
    """The reference answer to the instance `spec` over `words`."""
    rows = words[mask(words, index, spec)]
    sums = []
    for term in spec["sums"]:
        prod = np.ones(rows.shape[0], np.int64)
        for c in term:
            prod *= rows[:, index(c)].astype(np.int64)
        sums.append(int(prod.sum()))
    return Expected(count=int(rows.shape[0]), sums=tuple(sums))


def compare(answer, want: Expected) -> dict:
    """The numbers compared, to be held to LIMITS. `answer` is what the
    client holds: (count, sums)."""
    count, sums = answer
    bad = sum(1 for i, w in enumerate(want.sums)
              if i >= len(sums) or int(sums[i]) != w)
    return {"bad_count": abs(int(count) - want.count),
            "bad_sums": bad + max(0, len(sums) - len(want.sums))}


def control(words: np.ndarray, index, spec: dict):
    """The reference in bfloat16, in the client's answer format: the words
    rounded before the predicates and the products, and the count and
    each sum rounded to bfloat16."""
    want = expect(to_bf16(words), index, spec)

    def bf16(x: int) -> int:
        return int(to_bf16(np.float32([x]))[0])
    return bf16(want.count), tuple(bf16(s) for s in want.sums)


def _referenced(spec: dict) -> set:
    return ({c for c, _, _ in spec["select"]}
            | {c for term in spec["sums"] for c in term})


def kernel_bytes(spec: dict, word_bytes: int, n_rows: int) -> int:
    """The least HBM bytes the scan needs: every row's referenced words
    (the predicates' columns and the terms') read once."""
    return n_rows * len(_referenced(spec)) * word_bytes


def query_bytes(spec: dict, word_bytes: int, n_rows: int, width: int,
                count: int) -> int:
    """The HBM bytes the query's semantics require, whatever lowering runs
    it: the scan's (`kernel_bytes`) plus the answer written once."""
    return kernel_bytes(spec, word_bytes, n_rows) + answer_bytes(
        spec, word_bytes, width, count)


def answer_bytes(spec: dict, word_bytes: int, width: int,
                 count: int) -> int:
    """The least bytes an answer carries, however it is framed: the count
    and each sum, 8 bytes each."""
    return (1 + len(spec["sums"])) * ANSWER_WORD_BYTES
