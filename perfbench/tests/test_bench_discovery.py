"""A configuration, a traffic mix and a metric dropped into the
benchmark's directories are found by name: a new cell runs with new files
and new entries in BENCHMARK.json, and no existing file of the benchmark
changes."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from fvb import harness  # noqa: E402
from fvb import spec as fspec  # noqa: E402


def _digests(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_dropped_in_files_are_found_by_name(tmp_path, capsys):
    bench = tmp_path / BENCH.name
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = _digests(bench)

    cfg = json.loads((bench / "configs" / "farview_t64.json").read_text())
    cfg["name"] = "t64_narrow_keys"
    cfg["keys"] = dict(cfg["keys"], distinct=16)
    (bench / "configs" / "t64_narrow_keys.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "select_s10.json").write_text(json.dumps({
        "instances": [
            {"name": "S10", "smart": ["a0v", "a6v", "a1v"],
             "select": [["a6v", "<", 0.1], ["a1v", ">=", 3]]}]}))
    (bench / "metrics" / "survivors_per_q.py").write_text(
        "def read(run):\n"
        "    return sum(q.count for q in run.queries) / len(run.queries)\n")

    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "t64_narrow_keys", "source": "a test", "reduced": [],
        "file": f"{BENCH.name}/configs/t64_narrow_keys.json", "why": "a test"})
    doc["workloads"].append({
        "name": "t64nk.s10", "config": "t64_narrow_keys",
        "traffic": "select_s10", "chips": 1, "why": "a test"})
    doc["per_layer"].append({
        "name": "survivors_per_q", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "pool", "moves": "p50_ms",
        "workloads": ["t64nk.s10"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    spec = fspec.load("t64nk.s10", bench)
    assert spec.config["name"] == "t64_narrow_keys"
    assert spec.traffic["instances"][0]["name"] == "S10"
    assert [m["name"] for m in spec.per_layer][-1] == "survivors_per_q"

    for trace in ("0", "1"):
        rc = harness.main(["--workload", "t64nk.s10", "--seed", "9",
                           "--seconds", "0.3", "--trace", trace,
                           "--rehearse"], bench_dir=bench)
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["correct"] is True
        if trace == "1":
            assert out["metrics"]["survivors_per_q"]["value"] > 0
        else:
            assert "qps" in out["metrics"]

    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/t64_narrow_keys.json", "traffic/select_s10.json",
        "metrics/survivors_per_q.py"}
