"""The peaks table, the least-bytes count behind `exec_roofline`, and the
least bytes an answer carries over the wire (each verb's `query_bytes`
and `answer_bytes`)."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from fvb import peaks  # noqa: E402
from fvb import spec as fspec  # noqa: E402

N, W, WB = 1 << 24, 16, 4


def _instance(traffic: str, name: str) -> dict:
    doc = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return next(i for i in doc["instances"] if i["name"] == name)


def _verb(spec: dict):
    return fspec.module(BENCH / "verbs" / f"{spec['verb']}.py")


def test_s25p_by_hand():
    # reads a0v a0x a4v a4x a5v a5x (a4v is also the predicate's): 6 words
    # of every row; writes the same 6 words of every survivor
    spec = _instance("select", "S25P")
    assert _verb(spec).query_bytes(spec, WB, N, W, 1000) == \
        N * 6 * 4 + 1000 * 6 * 4 == 402_677_184


def test_s50_by_hand():
    # no projection: the whole 64 B row is read and every survivor written
    spec = _instance("select", "S50")
    assert _verb(spec).query_bytes(spec, WB, N, W, 3) == \
        N * 64 + 3 * 64 == 1_073_742_016


def test_g50_by_hand():
    # reads a4v (predicate), a0v (key), a1v-a3v (values): 5 words a row;
    # writes 1024 buckets x (key, count, sum/min/max of 3 values) = 11
    # words, and each overflow row's key and 3 values
    spec = _instance("group", "G50")
    assert _verb(spec).query_bytes(spec, WB, N, W, 10) == \
        N * 20 + 1024 * 11 * 4 + 10 * 4 * 4 == 335_589_536


@pytest.mark.parametrize("traffic,name,count,want", [
    # S25P: 6 projected words a survivor, however many the wire ships
    ("select", "S25P", 1000, 1000 * 6 * 4),
    # S50: whole 16-word rows
    ("select", "S50", 3, 3 * 16 * 4),
    # G50: each overflow row's key and 3 values; buckets may ship compacted
    ("group", "G50", 10, 10 * 4 * 4),
])
def test_answer_floor_by_hand(traffic, name, count, want):
    spec = _instance(traffic, name)
    assert _verb(spec).answer_bytes(spec, WB, W, count) == want


def test_v5e_peaks_and_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    assert p["source"] == "Google Cloud, TPU v5e"


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)
