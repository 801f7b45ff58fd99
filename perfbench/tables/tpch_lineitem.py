"""The table kind `tpch_lineitem`: TPC-H's `lineitem`, drawn by the rules
of the TPC Benchmark H specification, §4.2.3, from the run's seed.

The node holds one of dbgen's chunks (`-C <chunks>`) of the scale
factor's orders: chunk `seed % chunks`, its orders in key order, each
with 1-7 lines, until the table has its rows (the last order is cut at
the row limit). Per order: O_ORDERDATE uniform over [1992-01-01,
1998-12-31 - 151 days]. Per line: L_PARTKEY uniform over [1, SF *
200,000]; L_SUPPKEY from the spec's formula over the part's four
suppliers; L_QUANTITY 1-50; L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE
of the part; L_DISCOUNT 0.00-0.10 and L_TAX 0.00-0.08; L_SHIPDATE =
orderdate + 1-121 days, L_COMMITDATE = orderdate + 30-90, L_RECEIPTDATE
= shipdate + 1-30; L_LINESTATUS 'O' where the ship date is past
CURRENTDATE (1995-06-17), else 'F'; L_RETURNFLAG 'R' or 'A' at random
where the receipt date is not past it, else 'N'; L_SHIPINSTRUCT and
L_SHIPMODE uniform over their 4 and 7 values; L_COMMENT 10-43 bytes.

Every word is an integer below 2^24, exact in the f32 words the program
holds:

  * O_ORDERKEY is dbgen's sparse key (of each 32 keys the first 8), up to
    6 * 10^8 at SF 100, and L_PARTKEY up to 2 * 10^7: each is two words,
    hi * 2^16 + lo, as the paper's 8-byte attributes are two words;
  * money is in cents (L_EXTENDEDPRICE) and rates in hundredths
    (L_DISCOUNT, L_TAX), so a price times a discount is exact in units of
    10^-4;
  * dates are days since 1992-01-01;
  * L_RETURNFLAG (A 0, N 1, R 2), L_LINESTATUS (F 0, O 1),
    L_SHIPINSTRUCT and L_SHIPMODE are dictionary codes, in the spec's
    order of the values;
  * L_COMMENT is its bytes, three to a word (the first in the high
    byte), zero past its length: lowercase letters and spaces drawn at
    random, not dbgen's text grammar.

The configuration's `columns` give the 32 words of a row (128 B) in
order; the code here names them.
"""
from __future__ import annotations

import numpy as np

from fvb.data import Table, rng_of

ORDERDATE_LAST = 2556 - 151     # 1998-12-31 less 151 days, in days
CURRENTDATE = 1263              # 1995-06-17
COMMENT_WORDS = 15
_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
# every word of three letters, packed: word v holds letters v // 27^2,
# v // 27 % 27 and v % 27, the first in the high byte
_TRIPLES = ((_LETTERS[np.arange(27**3) // 729].astype(np.int32) << 16)
            | (_LETTERS[np.arange(27**3) // 27 % 27].astype(np.int32) << 8)
            | _LETTERS[np.arange(27**3) % 27])
_PARTIAL = np.array([0, 0xFF0000, 0xFFFF00], np.int32)  # a word's first bytes


def order_key(i: np.ndarray) -> np.ndarray:
    """dbgen's sparse order key of the 1-based order index i: the low
    three bits kept, two zero bits put above them."""
    return ((i >> 3) << 5) | (i & 7)


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of a part, in cents (§4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def supp_key(partkey: np.ndarray, i: np.ndarray, sf: int) -> np.ndarray:
    """L_SUPPKEY: supplier i (0-3) of the part (§4.2.3)."""
    s = sf * 10_000
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def _orders(rng, rows: int, first: int):
    """(order index, lines) of the orders that fill `rows`, from the
    1-based order index `first` on."""
    n = rows // 4 + 1024
    lines = rng.integers(1, 8, n)
    while lines.sum() < rows:
        lines = np.concatenate([lines, rng.integers(1, 8, n)])
    n = int(np.searchsorted(np.cumsum(lines), rows)) + 1
    return first + np.arange(n, dtype=np.int64), lines[:n]


def make_table(config: dict, seed: int, rows: int) -> Table:
    """The queried table, on the host."""
    cols = config["columns"]
    sf, chunks = int(config["scale_factor"]), int(config["chunks"])
    rng = rng_of(seed)
    per_chunk = sf * 1_500_000 // chunks
    idx, lines = _orders(rng, rows, (seed % chunks) * per_chunk + 1)
    odate = rng.integers(0, ORDERDATE_LAST + 1, idx.size)
    order = np.repeat(np.arange(idx.size), lines)[:rows]
    starts = np.cumsum(lines) - lines
    out = {}
    okey = order_key(idx)[order]
    out["l_orderkey_hi"], out["l_orderkey_lo"] = okey >> 16, okey & 0xFFFF
    out["l_linenumber"] = np.arange(rows) - starts[order] + 1
    part = rng.integers(1, sf * 200_000 + 1, rows)
    out["l_partkey_hi"], out["l_partkey_lo"] = part >> 16, part & 0xFFFF
    out["l_suppkey"] = supp_key(part, rng.integers(0, 4, rows), sf)
    qty = rng.integers(1, 51, rows)
    out["l_quantity"] = qty
    out["l_extendedprice"] = qty * retail_cents(part)
    out["l_discount"] = rng.integers(0, 11, rows)
    out["l_tax"] = rng.integers(0, 9, rows)
    od = odate[order]
    ship = od + rng.integers(1, 122, rows)
    out["l_shipdate"] = ship
    out["l_commitdate"] = od + rng.integers(30, 91, rows)
    receipt = ship + rng.integers(1, 31, rows)
    out["l_receiptdate"] = receipt
    out["l_linestatus"] = (ship > CURRENTDATE).astype(np.int64)
    out["l_returnflag"] = np.where(receipt <= CURRENTDATE,
                                   2 * rng.integers(0, 2, rows), 1)
    out["l_shipinstruct"] = rng.integers(0, 4, rows)
    out["l_shipmode"] = rng.integers(0, 7, rows)
    # built column by column, each column contiguous, then laid out as rows
    names = [c["name"] for c in cols]
    words = np.empty((len(cols), rows), np.float32)
    for name, vals in out.items():
        words[names.index(name)] = vals
    first = names.index("l_comment_0")
    words[first: first + COMMENT_WORDS] = comment_words(rng, rows)
    return Table(words=np.ascontiguousarray(words.T), columns=cols)


def comment_words(rng, rows: int) -> np.ndarray:
    """L_COMMENT of each row, 10-43 bytes, as COMMENT_WORDS words,
    column-major: (COMMENT_WORDS, rows)."""
    length = rng.integers(10, 44, rows).astype(np.int32)
    text = _TRIPLES[rng.integers(0, _TRIPLES.size, (COMMENT_WORDS, rows),
                                 dtype=np.int32)]
    w = np.arange(COMMENT_WORDS, dtype=np.int32)[:, None]
    full, part = length // 3, length % 3
    keep = np.where(w < full, 0xFFFFFF, np.where(w == full, _PARTIAL[part],
                                                 0))
    return text & keep


def other_table(config: dict, seed: int, t: int, rows: int):
    """The pool holds `lineitem` alone (`tables` 1)."""
    raise ValueError(f"{config['name']}: the pool holds lineitem alone; "
                     f"there is no table {t}")
