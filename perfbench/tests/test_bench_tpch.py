"""The TPC-H cell `tpch.q6`: the table kind `tpch_lineitem` keeps the
specification's rules (§4.2.3), the verb `aggregate` compares exactly and
its bfloat16 control fails, the cell's files are all found by name, and
`agg_roofline` reads what it should from a fold."""
import json
import sys
from datetime import date
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from fvb import spec as fspec  # noqa: E402
from fvb import traffic as ftraffic  # noqa: E402

SPEC = fspec.load("tpch.q6")
LINEITEM = SPEC.table_kind
AGG = fspec.module(BENCH / "verbs" / "aggregate.py")
ROWS = 20_000


@pytest.fixture(scope="module", params=[7, 2**31 + 5])
def table(request):
    t = LINEITEM.make_table(SPEC.config, request.param, ROWS)
    return {c["name"]: t.words[:, i].astype(np.int64)
            for i, c in enumerate(SPEC.config["columns"])}, t


def test_spec_load_finds_every_file():
    assert SPEC.config["name"] == "tpch_lineitem_sf100"
    assert SPEC.table_kind.__file__.endswith("tables/tpch_lineitem.py")
    assert set(SPEC.verbs) == {"aggregate"}
    assert [i["name"] for i in SPEC.traffic["instances"]] == [
        f"Q6_{y}" for y in range(1993, 1998)]
    assert {m["name"] for m in SPEC.per_layer} >= {"agg_roofline",
                                                  "exec_roofline"}
    assert SPEC.limits == {"bad_count": 0, "bad_sums": 0}
    assert len(SPEC.config["columns"]) == 32
    assert SPEC.config["rows"] * 128 == SPEC.config["pool_bytes"]


def test_dates_follow_the_order_date(table):
    c, _ = table
    ship, commit, receipt = (c["l_shipdate"], c["l_commitdate"],
                             c["l_receiptdate"])
    assert (receipt - ship).min() >= 1 and (receipt - ship).max() <= 30
    key = c["l_orderkey_hi"] * 2**16 + c["l_orderkey_lo"]
    # one order date per order fits every line's ship and commit dates
    lo = np.maximum(ship - 121, commit - 90)
    hi = np.minimum(ship - 1, commit - 30)
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    assert (np.maximum.reduceat(lo, starts)
            <= np.minimum.reduceat(hi, starts)).all()
    assert (np.minimum.reduceat(hi, starts) >= 0).all()
    last = (date(1998, 12, 31) - date(1992, 1, 1)).days - 151
    assert (np.maximum.reduceat(lo, starts) <= last).all()


def test_flags_against_currentdate(table):
    c, _ = table
    now = (date(1995, 6, 17) - date(1992, 1, 1)).days
    assert LINEITEM.CURRENTDATE == now
    np.testing.assert_array_equal(c["l_linestatus"],
                                  (c["l_shipdate"] > now).astype(np.int64))
    late = c["l_receiptdate"] > now
    assert (c["l_returnflag"][late] == 1).all()                 # N
    assert set(np.unique(c["l_returnflag"][~late])) == {0, 2}   # A, R


def test_price_and_keys(table):
    c, _ = table
    part = c["l_partkey_hi"] * 2**16 + c["l_partkey_lo"]
    assert part.min() >= 1 and part.max() <= 100 * 200_000
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    np.testing.assert_array_equal(c["l_extendedprice"],
                                  c["l_quantity"] * retail)
    assert c["l_quantity"].min() >= 1 and c["l_quantity"].max() <= 50
    assert c["l_discount"].max() <= 10 and c["l_tax"].max() <= 8
    for k in ("l_orderkey_lo", "l_partkey_lo"):
        assert c[k].min() >= 0 and c[k].max() < 2**16
    key = c["l_orderkey_hi"] * 2**16 + c["l_orderkey_lo"]
    assert ((key >> 3) & 3 == 0).all() and (np.diff(key) >= 0).all()
    s = 100 * 10_000
    cands = [(part + i * (s // 4 + (part - 1) // s)) % s + 1
             for i in range(4)]
    assert np.any([c["l_suppkey"] == x for x in cands], axis=0).all()
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    ln = c["l_linenumber"]
    assert (ln[starts] == 1).all() and ln.max() <= 7
    assert (np.diff(ln)[np.diff(key) == 0] == 1).all()


def test_every_word_is_exact_and_comments_are_text(table):
    _, t = table
    assert np.abs(t.words).max() < 2**24
    assert (t.words == np.rint(t.words)).all()
    words = t.words[:, -LINEITEM.COMMENT_WORDS:].astype(np.int64)
    text = np.stack([(words >> s) & 0xFF for s in (16, 8, 0)],
                    axis=2).reshape(len(words), -1)
    length = (text > 0).sum(axis=1)
    assert length.min() >= 10 and length.max() <= 43
    assert set(np.unique(text[text > 0])) <= set(b"abcdefghijklmnopqrstu"
                                                  b"vwxyz ")


def test_q6_selects_about_two_percent(table):
    _, t = table
    for inst in SPEC.traffic["instances"]:
        want = AGG.expect(t.words, t.index, inst)
        assert 0.012 < want.count / ROWS < 0.028, (inst["name"], want)


@pytest.mark.parametrize("plant,bad", [
    (lambda a: (a[0], (a[1][0] + 1,)), {"bad_count": 0, "bad_sums": 1}),
    (lambda a: (a[0], (a[1][0] - 1,)), {"bad_count": 0, "bad_sums": 1}),
    (lambda a: (a[0] + 1, a[1]), {"bad_count": 1, "bad_sums": 0}),
    (lambda a: (a[0], ()), {"bad_count": 0, "bad_sums": 1}),
    (lambda a: a, {"bad_count": 0, "bad_sums": 0}),
], ids=["sum_plus_one", "sum_minus_one", "count_plus_one", "sum_missing",
        "exact"])
def test_compare_catches_an_off_by_one(table, plant, bad):
    _, t = table
    inst = SPEC.traffic["instances"][1]
    want = AGG.expect(t.words, t.index, inst)
    assert AGG.compare(plant((want.count, want.sums)), want) == bad


def test_bf16_control_fails_every_instance(table):
    _, t = table
    for inst in SPEC.traffic["instances"]:
        want = AGG.expect(t.words, t.index, inst)
        got = AGG.compare(AGG.control(t.words, t.index, inst), want)
        assert got["bad_sums"] > AGG.LIMITS["bad_sums"], inst["name"]


def test_bytes_by_hand():
    inst = SPEC.traffic["instances"][0]
    # four referenced columns (ship date, discount, quantity, price)
    assert AGG.kernel_bytes(inst, 4, 1000) == 16_000
    assert AGG.answer_bytes(inst, 4, 32, 123) == 16
    assert AGG.query_bytes(inst, 4, 1000, 32, 123) == 16_016
    pipe = ftraffic.pipeline_of(inst, SPEC.config, AGG)
    assert type(pipe[-1]).__name__ == "Aggregate"


def _run(top_ops, kernel_s, n_q=4, kind="TPU v5 lite"):
    inst = ftraffic.instances(SPEC)[0]
    fold = SimpleNamespace(n_chips=1, kernel_s=kernel_s, top_ops=top_ops)
    return SimpleNamespace(fold=fold, queries=[SimpleNamespace(
        inst=inst.name)] * n_q, insts={inst.name: inst},
        spec=SPEC, n_rows=SPEC.config["rows"], device_kind=kind)


@pytest.mark.parametrize("top_ops,want", [
    ([("%select_aggregate.1 custom-call", 0.2),
      ("%fusion.3 fusion", 0.5)], True),
    ([("%select_aggregate.1 custom-call", 0.2),
      ("%ctr_crypt.2 custom-call", 0.1)], False),
    ([("%fusion.3 fusion", 0.5)], False),
], ids=["alone", "beside_another_kernel", "absent"])
def test_agg_roofline_by_hand(top_ops, want):
    read = fspec.reader(BENCH, "agg_roofline")
    got = read(_run(top_ops, kernel_s=0.2))
    if not want:
        assert got is None
        return
    # 4 queries x 25,165,824 rows x 4 columns x 4 B at 819 GB/s, over 0.2 s
    need = 4 * 25_165_824 * 16 / 819e9
    assert got == pytest.approx(100 * need / 0.2)


def test_benchmark_entries():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}["tpch.q6"]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    for m in bench["per_layer"]:
        assert "tpch.q6" in m["workloads"], m["name"]
