"""Peaks of the chips the benchmark runs on: what a roofline share is
measured against. The least bytes a query needs are its verb's
(`verbs/<verb>.py`: `query_bytes`, `answer_bytes`).

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
