"""Range predicates and the exact scalar aggregate (TPC-H Q6's shape).

The contract under test:

(a) several predicates on one column are ANDed, in both lowerings (XLA
    and the Pallas kernels in interpret mode) and in the group path:
    two bounds, three bounds, BETWEEN, an equality beside a bound — where
    keeping only a column's last predicate would give another answer;
(b) `Aggregate` counts the selected rows and sums each term (a column or
    the product of two) exactly, equal to the plain int64 reference
    (`ref.aggregate_exact`) and to numpy, at operands of +-(2^24 - 1)
    whose sums no int32 or f32 accumulator holds;
(c) what cannot be exact is refused with a typed error: a term on an f32
    column, a column needing more than two predicate slots, a predicate
    or term on a column SmartAddress does not read;
(d) the operator and its "scalars" result cross the wire as they are,
    sums past 64 bits included, and a 3-node `FarCluster` gives one
    node's answer.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import operators as op
from repro.core.client import (FViewNode, alloc_table_mem, farview_request,
                               open_connection, table_write)
from repro.core.cluster import FarCluster
from repro.core.pipeline import CompiledPipeline
from repro.core.table import Column, FTable
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.net import wire

N = 900             # not a multiple of any block: every tail is padded
BIG = 2**24 - 1
COLS = (Column("k", "i32"), Column("d", "i32"), Column("p", "i32"),
        Column("q", "i32"), Column("f", "f32"), Column("s", "i32"))
LOWERINGS = {"xla": True, "kernel": False}     # CompiledPipeline interpret


@pytest.fixture(scope="module")
def words():
    rng = np.random.default_rng(16)
    w = np.zeros((N, len(COLS)), np.float32)
    w[:, 0] = rng.integers(0, 13, N)                      # group key
    w[:, 1] = rng.integers(0, 40, N)                      # range column
    w[:, 2] = rng.integers(-BIG, BIG + 1, N)
    w[:, 3] = rng.integers(-BIG, BIG + 1, N)
    w[:, 4] = rng.random(N)
    w[:, 5] = rng.integers(0, 11, N)
    w[:300, 2] = BIG                    # products of 2^48: their sums pass
    w[:300, 3] = BIG                    # 2^53, where f64 stops being exact
    w[300:400, 2] = -BIG
    return w


def run(pipe, words, interpret, n_nodes=None, partitioner="range"):
    ft = FTable("t", COLS, n_rows=words.shape[0])
    if n_nodes is None:
        node = FViewNode(64 * 2**20, interpret=interpret)
        qp = open_connection(node)
        alloc_table_mem(qp, ft)
        table_write(qp, ft, words)
        return farview_request(qp, ft, pipe).finalize()
    cl = FarCluster(n_nodes, partitioner=partitioner)
    cqp = cl.open_connection()
    keys = None if partitioner == "range" else words[:, 0].astype(np.int32)
    ct = cl.alloc_table_mem(cqp, ft, keys=keys)
    cl.table_write(cqp, ct, words)
    return cl.farview_request(cqp, ct, pipe).finalize()


NP_OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
          ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


def np_mask(words, preds):
    m = np.ones(words.shape[0], bool)
    for p in preds:
        m &= NP_OPS[p.op](words[:, [c.name for c in COLS].index(p.col)],
                          np.float32(p.value))
    return m


def last_wins(words, preds):
    """The old plan's answer: one op per column, the last one kept."""
    return np_mask(words, list({p.col: p for p in preds}.values()))


P = op.Predicate
RANGES = {
    "two_bounds": (P("d", ">=", 10.0), P("d", "<", 25.0)),
    "three_bounds": (P("d", ">", 5.0), P("d", "<", 30.0), P("d", ">=", 12.0)),
    "between": (P("d", ">=", 8.0), P("d", "<=", 20.0), P("s", "<", 6.0)),
    "eq_and_bound": (P("d", "==", 17.0), P("d", "<", 30.0)),
    "two_not_equal": (P("s", "!=", 3.0), P("s", "!=", 7.0)),
}


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
@pytest.mark.parametrize("case", sorted(RANGES))
def test_range_predicates_select(words, case, lowering):
    preds = RANGES[case]
    res = run((op.Select(preds),), words, LOWERINGS[lowering])
    m = np_mask(words, preds)
    assert m.sum() != last_wins(words, preds).sum()
    assert res.count == int(m.sum())
    np.testing.assert_array_equal(np.asarray(res.rows)[: res.count],
                                  words[m])


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
@pytest.mark.parametrize("case", ["two_bounds", "three_bounds", "between"])
def test_range_predicates_group(words, case, lowering):
    preds = RANGES[case]
    res = run((op.Select(preds), op.GroupBy("k", ("s",), n_buckets=64)),
              words, LOWERINGS[lowering])
    from repro.core.client import merge_group_partials
    ft = FTable("t", COLS, n_rows=N)
    groups = merge_group_partials(
        ft, (op.Select(preds), op.GroupBy("k", ("s",), n_buckets=64)),
        [res]).groups
    m = np_mask(words, preds)
    want = ref.group_aggregate_exact(words[m, 0].astype(np.int32),
                                     words[m][:, [5]])
    assert sorted(groups) == sorted(want)
    for key, (c, s, _, _) in want.items():
        assert int(groups[key][0]) == c
        assert float(np.asarray(groups[key][1])[0]) == float(s[0])


def test_kernel_predicate_slots_match_xla(words):
    """The kernel and the XLA lowering over (A, S) slots, rows past
    n_valid masked: byte-identical."""
    plan = CompiledPipeline(FTable("t", COLS, n_rows=N),
                            (op.Select(RANGES["between"]
                                       + RANGES["two_bounds"][:1]),),
                            interpret=True)
    assert plan.sel_ops.shape == (len(COLS), 2)
    valid = jnp.arange(N) < 700
    want, n_want = kops.select_project_xla(jnp.asarray(words), plan.sel_ops,
                                           plan.sel_vals, plan.proj_mask,
                                           valid)
    got, n_got = kops.select_project_cols(jnp.asarray(words).T, plan.sel_ops,
                                          plan.sel_vals, plan.proj_mask, 700,
                                          interpret=True)
    assert int(n_got) == int(n_want)
    np.testing.assert_array_equal(np.asarray(got).T, np.asarray(want))


@pytest.mark.parametrize("preds,want", [
    ((P("d", ">", 1.0), P("d", ">=", 4.0), P("d", "<=", 9.0),
      P("d", "<", 9.0)), [(">=", 4.0), ("<", 9.0)]),
    ((P("d", "==", 3.0), P("d", "==", 5.0)), [(">=", 5.0), ("<=", 3.0)]),
    ((P("d", ">=", 2.0), P("d", ">", 2.0)), [(">", 2.0)]),
    ((P("d", "<", float("nan")), P("d", "<", 3.0)), [("<", "nan")]),
    ((P("d", "!=", 1.0),), [("!=", 1.0)]),
], ids=["tightest", "two_equalities", "strict_wins", "nan_holds_nowhere",
        "lone"])
def test_column_slots_merge_bounds(preds, want):
    got = op.column_slots(preds)["d"]
    assert [(o, "nan" if np.isnan(v) else float(v)) for o, v in got] == want


TERMS = {
    "product": (("p", "q"),),
    "column": (("p",),),
    "q6_shape": (("p", "s"), ("s",), ("q", "q")),
}


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
@pytest.mark.parametrize("terms", sorted(TERMS))
def test_aggregate_is_exact(words, terms, lowering):
    preds = (P("d", ">=", 3.0), P("d", "<", 36.0), P("s", "<=", 9.0))
    pipe = (op.Select(preds), op.Aggregate(TERMS[terms]))
    res = run(pipe, words, LOWERINGS[lowering])
    names = [c.name for c in COLS]
    m = np_mask(words, preds)
    want = tuple(sum(int(np.prod([int(x) for x in row]))
                     for row in words[m][:, [names.index(c) for c in t]])
                 for t in TERMS[terms])
    assert res.kind == "scalars"
    assert res.scalars == {"count": int(m.sum()), "sums": want}
    assert res.count == int(m.sum())
    plan = CompiledPipeline(FTable("t", COLS, n_rows=N), pipe,
                            interpret=True)
    assert ref.aggregate_exact(words, plan.sel_ops, plan.sel_vals,
                                plan.agg_terms) == (int(m.sum()), want)
    # neither an int32 nor an f32 accumulator holds these sums
    assert any(abs(s) >= 2**31 for s in want)
    f32 = [float(np.sum(np.prod(words[m][:, [names.index(c) for c in t]],
                                axis=1, dtype=np.float32), dtype=np.float32))
           for t in TERMS[terms]]
    assert f32 != [float(s) for s in want]


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_aggregate_past_int64(lowering):
    """40,000 rows of (2^24 - 1)^2 sum past 2^63: the kernel's limbs, the
    XLA lowering's and the Python ints that combine them stay exact."""
    n = 40_000
    w = np.zeros((n, len(COLS)), np.float32)
    w[:, 2] = w[:, 3] = BIG
    want = n * BIG * BIG
    assert want > 2**63
    res = run((op.Aggregate((("p", "q"),)),), w, LOWERINGS[lowering])
    assert res.scalars == {"count": n, "sums": (want,)}


@pytest.mark.parametrize("block_rows", [256, 2048])
def test_aggregate_kernel_limbs(words, block_rows):
    """The kernel alone, rows past n_valid masked, every block size."""
    ops = np.zeros((len(COLS), 2), np.int32)
    vals = np.zeros((len(COLS), 2), np.float32)
    ops[1] = (ref.OP_GE, ref.OP_LT)
    vals[1] = (4.0, 33.0)
    terms = ((2, 3), (5,))
    limbs = kops.select_aggregate_cols(jnp.asarray(words).T, ops, vals,
                                       terms, 777, block_rows=block_rows,
                                       interpret=True)
    valid = np.arange(N) < 777
    assert ref.limb_totals(limbs, 2) == ref.aggregate_exact(
        words, ops, vals, terms, valid)
    xla = kops.select_aggregate_xla(jnp.asarray(words), ops, vals, terms,
                                    jnp.asarray(valid))
    assert ref.limb_totals(xla, 2) == ref.limb_totals(limbs, 2)


def test_product_digits_cover_the_extremes():
    x = np.array([-2**24, -BIG, -4097, -4096, -1, 0, 1, 4095, 4096, BIG,
                  2**24], np.int32)
    a, b = np.meshgrid(x, x)
    d = ref.product_digits(jnp.asarray(a), jnp.asarray(b))
    got = sum(np.asarray(dj).astype(object) * (1 << (12 * j))
              for j, dj in enumerate(d))
    assert (got == a.astype(object) * b.astype(object)).all()
    assert all(0 <= int(np.asarray(dj).min()) and int(np.asarray(dj).max())
               < 4096 for dj in d[:4])


@pytest.mark.parametrize("pipe,error", [
    ((op.Aggregate((("p", "f"),)),), op.AggregateTermError),
    ((op.Aggregate((("p", "q", "s"),)),), op.AggregateTermError),
    ((op.Select((P("d", ">", 1.0), P("d", "<", 9.0), P("d", "!=", 4.0))),),
     op.PredicateSlotError),
    ((op.SmartAddress(("p", "q")), op.Select((P("d", "<", 9.0),))),
     op.UnreadPredicateError),
    ((op.SmartAddress(("d",)), op.Select((P("d", "<", 9.0),)),
      op.Aggregate((("p",),))), op.UnreadPredicateError),
    ((op.Aggregate((("p",),)), op.GroupBy("k", ("s",))), op.PlanError),
], ids=["f32_term", "three_column_term", "slot_overflow", "smart_unread",
        "smart_unread_term", "aggregate_and_group"])
def test_typed_errors(pipe, error):
    with pytest.raises(error):
        CompiledPipeline(FTable("t", COLS, n_rows=N), pipe, interpret=True)


def test_smart_address_keeps_read_predicates(words):
    """A predicate on a column SmartAddress reads still filters."""
    pipe = (op.SmartAddress(("d", "s")),
            op.Select((P("d", ">=", 10.0), P("d", "<", 20.0))),
            op.Aggregate((("s",), ("d", "s"))))
    res = run(pipe, words, True)
    m = np_mask(words, pipe[1].predicates)
    assert res.scalars["count"] == int(m.sum())
    assert res.scalars["sums"][0] == int(words[m, 5].astype(np.int64).sum())


@pytest.mark.parametrize("value", [
    op.Aggregate((("l_extendedprice", "l_discount"), ("l_quantity",))),
    {"kind": "scalars", "count": 12,
     "scalars": {"count": 12, "sums": (2**70 + 3, -(2**64) - 1, 5)}},
], ids=["operator", "scalars_payload"])
def test_wire_round_trip(value):
    assert wire.decode_value(wire.encode_value(value)) == value


@pytest.mark.parametrize("partitioner", ["range", "hash", "skew"])
def test_cluster_q6_equals_one_node(words, partitioner):
    pipe = (op.Select((P("d", ">=", 5.0), P("d", "<", 31.0),
                       P("s", ">=", 2.0), P("s", "<=", 8.0))),
            op.Aggregate((("p", "s"), ("q",))))
    one = run(pipe, words, True)
    three = run(pipe, words, True, n_nodes=3, partitioner=partitioner)
    assert three.kind == "scalars"
    assert three.scalars == one.scalars
    assert three.read_bytes == one.read_bytes


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_stacked_round_answers_each_request(words, lowering):
    """Two clients' Q6 in one scheduling round: one stacked executable,
    each request's own answer (the kernel runs once per request)."""
    from repro.core.client import submit_request
    node = FViewNode(64 * 2**20, interpret=LOWERINGS[lowering])
    pipe = (op.Select((P("d", ">=", 4.0), P("d", "<", 29.0))),
            op.Aggregate((("p", "s"),)))
    pends = []
    for rows in (N, N - 211):
        qp = open_connection(node)
        ft = FTable("t", COLS, n_rows=rows)
        alloc_table_mem(qp, ft)
        table_write(qp, ft, words[:rows])
        pends.append((submit_request(qp, ft, pipe), rows))
    before = node.dispatches
    node.flush()
    assert node.dispatches == before + 1
    for pend, rows in pends:
        m = np_mask(words[:rows], pipe[0].predicates)
        want = int((words[:rows][m, 2].astype(np.int64)
                    * words[:rows][m, 5].astype(np.int64)).sum())
        assert pend.result.scalars == {"count": int(m.sum()),
                                       "sums": (want,)}
