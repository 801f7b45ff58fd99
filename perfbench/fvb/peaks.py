"""Peaks of the chips the benchmark runs on, and the least bytes a query
needs: what a roofline share is measured against, and what its answer
must carry over the wire.

The table is keyed by `device_kind` as JAX reports it. A kind that is not
in it is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to perfbench/fvb/peaks.py with its source") from None


def query_bytes(spec: dict, word_bytes: int, n_rows: int, width: int,
                count: int = 0, n_overflow: int = 0) -> int:
    """The HBM bytes the query's semantics require, whatever lowering runs
    it: every row's referenced words read once, plus every survivor's
    output words written once. A group-by writes its bucket table (per
    bucket: key, count, and sum, min and max of each value) and its
    overflow rows (key and values) once. A pre-decrypt reads the same
    words: the CTR keystream is positional, so any word decrypts alone.

    `count` is the survivor count, `n_overflow` the overflow rows."""
    sel = {c for c, _, _ in spec.get("select", ())}
    if "group" in spec:
        g = spec["group"]
        ref = sel | {g["key"]} | set(g["values"])
        nv = len(g["values"])
        out = g["n_buckets"] * (2 + 3 * nv) + n_overflow * (1 + nv)
        return (n_rows * len(ref) + out) * word_bytes
    cols = spec.get("smart") or spec.get("project")
    k = width if cols is None else len(cols)
    ref = width if cols is None else len(sel | set(cols))
    return (n_rows * ref + count * k) * word_bytes


def answer_bytes(spec: dict, word_bytes: int, width: int,
                 count: int) -> int:
    """The least bytes an answer carries, however it is framed: every
    survivor's output words, or a group-by's overflow rows (key and
    values), which the client merges itself. `count` is the survivor
    count, or a group-by's overflow rows."""
    if "group" in spec:
        return count * (1 + len(spec["group"]["values"])) * word_bytes
    cols = spec.get("smart") or spec.get("project")
    return count * (width if cols is None else len(cols)) * word_bytes
