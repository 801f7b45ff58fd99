"""A configuration, a traffic mix, a metric, a verb and a table kind
dropped into the benchmark's directories are found by name: a new cell
runs with new files and new entries in BENCHMARK.json, and no existing
file of the benchmark changes."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

import pytest

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
            {"name": "S10", "verb": "rows",
             "smart": ["a0v", "a6v", "a1v"],
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


DERIVED_TABLE = '''"""A table kind whose second column is derived from its first."""
import numpy as np

from fvb.data import Table, rng_of


def make_table(config, seed, rows):
    x = rng_of(seed).integers(0, 1000, rows).astype(np.float32)
    return Table(words=np.stack([x, 3 * x + 1], axis=1),
                 columns=config["columns"])


def other_table(config, seed, t, rows):
    import jax.numpy as jnp
    return jnp.asarray(make_table(config, seed + t, rows).words.reshape(-1))
'''

COUNT_VERB = '''"""A verb whose answer is the survivor count alone."""
from fvb.reference import mask, to_bf16

KEEP = None
LIMITS = {"bad_survivors": 0}


def pipeline(spec, config):
    from repro.core import operators as op
    return [op.Select(tuple(op.Predicate(c, o, float(v))
                            for c, o, v in spec["select"]))]


def answer(res, ft, pipeline):
    n = int(res.count)
    return n + OFF, n


def expect(words, index, spec):
    return int(mask(words, index, spec).sum())


def compare(answer, want):
    return {"bad_survivors": abs(int(answer) - want)}


def control(words, index, spec):
    return expect(to_bf16(words), index, spec)


def query_bytes(spec, word_bytes, n_rows, width, count):
    return n_rows * len({c for c, _, _ in spec["select"]}) * word_bytes


def answer_bytes(spec, word_bytes, width, count):
    return word_bytes
'''


def _drop_in_count_cell(bench: Path, root_doc: Path) -> None:
    cfg = {"name": "derived_pairs", "table": "derived", "rows": 4096,
           "pool_bytes": 8 << 21, "tables": 2, "n_regions": 6,
           "max_payload": 1 << 20, "word_bytes": 4,
           "columns": [{"name": "x", "dtype": "i32"},
                       {"name": "y", "dtype": "i32"}]}
    (bench / "configs" / "derived_pairs.json").write_text(json.dumps(cfg))
    (bench / "tables" / "derived.py").write_text(DERIVED_TABLE)
    (bench / "traffic" / "count_y.json").write_text(json.dumps({
        "instances": [{"name": "C1", "verb": "count",
                       "select": [["y", ">", 1500], ["x", "<", 900]]}]}))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "derived_pairs", "source": "a test", "reduced": [],
        "file": f"{BENCH.name}/configs/derived_pairs.json", "why": "a test"})
    doc["workloads"].append({
        "name": "dp.count", "config": "derived_pairs", "traffic": "count_y",
        "chips": 1, "why": "a test"})
    root_doc.write_text(json.dumps(doc))


def test_dropped_in_verb_and_table_kind_run_a_cell(tmp_path, capsys):
    bench = tmp_path / BENCH.name
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = _digests(bench)
    _drop_in_count_cell(bench, tmp_path / "BENCHMARK.json")

    outs = {}
    for off in (0, 1):          # the verb as written, then off by one
        (bench / "verbs" / "count.py").write_text(
            COUNT_VERB.replace("OFF", str(off)))
        rc = harness.main(["--workload", "dp.count", "--seed", "12",
                           "--seconds", "0.3", "--trace", "0",
                           "--rehearse"], bench_dir=bench)
        assert rc == 0
        outs[off] = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
    assert outs[0]["correct"] is True
    assert outs[0]["checks"]["bad_survivors"] == {"value": 0, "limit": 0}
    assert "qps" in outs[0]["metrics"]
    assert outs[1]["correct"] is False
    assert outs[1]["checks"]["bad_survivors"]["value"] >= 1

    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/derived_pairs.json", "tables/derived.py",
        "traffic/count_y.json", "verbs/count.py"}


@pytest.mark.parametrize("missing", ["verbs/count.py", "tables/derived.py"])
def test_a_missing_verb_or_table_file_is_named(missing, tmp_path):
    bench = tmp_path / BENCH.name
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    _drop_in_count_cell(bench, tmp_path / "BENCHMARK.json")
    (bench / "verbs" / "count.py").write_text(COUNT_VERB)
    (bench / missing).unlink()
    with pytest.raises(FileNotFoundError, match=missing):
        fspec.load("dp.count", bench)
