"""Each cell, rehearsed at a tiny size on the CPU, prints a last line with
exactly the contract's keys and `correct` true; without the rehearsal
switch, or without the program beside it, the benchmark prints nothing
and fails."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from fvb import harness  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _last_line(capsys) -> dict:
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell, capsys):
    rc = harness.main(["--workload", cell, "--seed", str(2**31 + 11),
                       "--seconds", "0.5", "--trace", "0", "--rehearse"])
    assert rc == 0
    out = _last_line(capsys)
    assert list(out) == KEYS
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])
    assert out["checks"] and all(c["value"] <= c["limit"]
                                 for c in out["checks"].values())


def test_traced_rehearsal_reports_per_layer_counters(capsys):
    rc = harness.main(["--workload", "fv64.group", "--seed", "5",
                       "--seconds", "0.3", "--trace", "1", "--rehearse"])
    assert rc == 0
    out = _last_line(capsys)
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert out["correct"] is True
    # no device plane on the CPU: the device metrics stay out of the line
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert out["metrics"]["pool_read_B_per_q"]["value"] == 4096 * 64
    assert "idle_share" not in out["metrics"]


def test_no_chip_prints_nothing_and_fails(capsys):
    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_without_the_program_prints_nothing_and_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, f"{BENCH.name}/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
