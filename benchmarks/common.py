"""Shared benchmark utilities: timing, CSV rows, the LCPU/RCPU baselines.

Baselines (paper §6.1):
  FV    — Farview pipeline on the pool (fused jitted request path)
  LCPU  — local buffer cache + numpy processing on the "client CPU"
  RCPU  — remote buffer cache: full table "shipped" (bytes accounted), then
          numpy processing client-side.
On this container both baselines run on the same CPU, so wall-times are
indicative; the byte accounting (shipped/read) is exact and is the number
the paper's economics rest on. Each row reports both.

Timing is BLOCKING: `timeit` materializes whatever the closure returns
inside the timed region — lazy `PipelineResult`s are finalized and device
arrays are `jax.block_until_ready`-ed — so FV rows measure completed work,
never async dispatch. Reported value is the p50 (median) across repeats.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

ROWS: list[dict] = []

# Quick/smoke mode (CI benchmark job): single warmup + single repeat and
# reduced problem sizes where a bench opts in via `quick()`. Enabled by
# `benchmarks.run --quick` or the FARVIEW_BENCH_QUICK env var. Timings in
# this mode are indicative only — the JSON artifact tracks that the bench
# *runs* and its exact byte accounting, not p50 stability.
QUICK = os.environ.get("FARVIEW_BENCH_QUICK", "") not in ("", "0")


def quick() -> bool:
    return QUICK


def _materialize(x) -> None:
    """Block on the timed closure's result: finalize lazy pipeline results,
    wait for device arrays; values that are not arrays pass through. A
    device error propagates: a failed run must never pass as a fast one."""
    if x is None:
        return
    if hasattr(x, "finalize"):
        x.finalize()
        return
    if isinstance(x, (list, tuple)):
        for e in x:
            _materialize(e)
        return
    jax.block_until_ready(x)


def timeit(fn, *, repeat: int = 5, warmup: int = 2) -> float:
    """p50 wall time of `fn()` including result materialization (seconds)."""
    if QUICK:
        repeat, warmup = 1, 1
    for _ in range(warmup):
        _materialize(fn())
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        _materialize(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def cluster_padding(*ctables) -> tuple[int, int]:
    """(valid_rows, padded_rows) across cluster tables: what the nodes'
    shape-bucketed executables actually run vs the rows that carry data.
    The gap was the ROADMAP's bucketing-waste item — hash partitions of
    pow2 tables land at n/k+eps rows; the quarter-octave `shape_bucket`
    ladder caps the round-up at 1.25x where pow2 paid up to 2x —
    reported per bench row so the waste stays visible in BENCH json."""
    from repro.core.operators import shape_bucket
    valid = padded = 0
    for ct in ctables:
        for p in ct.parts:
            if p is not None and p.n_rows:
                valid += p.n_rows
                padded += shape_bucket(p.n_rows)
    return valid, padded


def row(bench: str, name: str, us: float, **derived):
    r = {"bench": bench, "name": name, "us_per_call": round(us, 1)}
    r.update(derived)
    ROWS.append(r)
    return r


def _plain(v):
    """JSON/CSV-safe scalar (numpy ints/floats -> python)."""
    if isinstance(v, (np.integer, np.floating)):
        return v.item()
    return v


def rows_as_records() -> list[dict]:
    return [{k: _plain(v) for k, v in r.items()} for r in ROWS]


def bench_meta() -> dict:
    """Provenance stamp for --json output: git commit, jax version, device
    platform, kind and count, quick-mode flag. BENCH_*.json files carry it so the perf
    trajectory is comparable PR over PR (and the CI regression guard can
    refuse to compare quick-mode against full-mode numbers)."""
    import subprocess
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or None
    except Exception:
        commit = None
    return {
        "git_commit": commit,
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "quick": QUICK,
    }


def write_json(path: str) -> None:
    """Write {"meta": ..., "rows": [...]} (the post-PR4 BENCH format; the
    regression guard still reads the older bare-list files)."""
    import json
    with open(path, "w") as f:
        json.dump({"meta": bench_meta(), "rows": rows_as_records()}, f,
                  indent=2, default=str)


def print_csv():
    keys = ["bench", "name", "us_per_call"]
    extra = sorted({k for r in ROWS for k in r} - set(keys))
    cols = keys + extra
    print(",".join(cols))
    for r in ROWS:
        print(",".join(str(r.get(k, "")) for k in cols))
