"""What one run is, found by name in `BENCHMARK.json` and the files beside it.

A workload names a configuration and a traffic mix. The configuration's
file is the one `BENCHMARK.json` gives; the traffic mix is
`traffic/<name>.json`; every metric is read by `metrics/<name>.py`, a
module with one function, `read(run)`, that returns a number or None.
Adding a cell, a mix or a metric is adding files and entries: nothing
here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclass
class Spec:
    root: Path              # the checkout: BENCHMARK.json lives here
    bench_dir: Path         # the benchmark's own directory
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list        # metric entries of BENCHMARK.json, this cell's
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(workload: str, bench_dir: Path = BENCH_DIR) -> Spec:
    """The cell `workload` of the BENCHMARK.json beside `bench_dir`;
    KeyError if absent."""
    root = bench_dir.parent
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    return Spec(root=root, bench_dir=bench_dir, workload=cell,
                config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)])


def reader(bench_dir: Path, name: str):
    """`read(run)` of metrics/<name>.py."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
