"""What one run is, found by name in `BENCHMARK.json` and the files beside it.

A workload names a configuration and a traffic mix. The configuration's
file is the one `BENCHMARK.json` gives, and its `table` key names its
table kind, `tables/<kind>.py`; the traffic mix is `traffic/<name>.json`,
and each of its instances names its verb in a `verb` key,
`verbs/<verb>.py`; every metric is read by `metrics/<name>.py`, a module
with one function, `read(run)`, that returns a number or None. Adding a
cell, a mix, a verb, a table kind or a metric is adding files and
entries: nothing here names one.

Besides what its table kind reads, the harness reads a configuration's
`name`, `rows`, `pool_bytes`, `tables`, `n_regions`, `max_payload`,
`word_bytes`, `columns` (each with its `name` and `dtype`) and
`encrypted_at_rest` (null, or the cipher's `key` and `nonce`).

A table kind has `make_table(config, seed, rows)`, the queried table as
`fvb.data.Table` (host numpy, which the reference reads), and
`other_table(config, seed, t, rows)`, table t of the pool as a flat device
array, ciphertext where the configuration holds its tables encrypted.

A verb has `pipeline(spec, config)` (the program's operators; the harness
puts the configuration's `Crypt(pre)` in front), `answer(res, ft,
pipeline)` (what the client holds once the result is final, and the count
its byte floors take), `KEEP` (answers kept per instance for the check,
None for all), `expect`, `compare`, `control` and `LIMITS` (the plain
reference, the numbers compared and their limits, the control), and
`query_bytes` and `answer_bytes` (the least bytes the query moves in HBM
and carries over the wire).
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

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
    table_kind: ModuleType  # tables/<config["table"]>.py
    verbs: dict             # verb name -> verbs/<verb>.py, this mix's

    @property
    def limits(self) -> dict:
        """The limit of each number this mix's verbs compare."""
        return {k: v for verb in self.verbs.values()
                for k, v in verb.LIMITS.items()}


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def module(path: Path) -> ModuleType:
    """The module in the file `path`; FileNotFoundError, naming the file,
    if there is none."""
    if not path.is_file():
        raise FileNotFoundError(f"perfbench: no file {path}")
    name = "perfbench_" + "_".join(path.with_suffix("").parts[-2:])
    name = name.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod         # a dataclass looks its module up
    mod_spec.loader.exec_module(mod)
    return mod


def load(workload: str, bench_dir: Path = BENCH_DIR) -> Spec:
    """The cell `workload` of the BENCHMARK.json beside `bench_dir`;
    KeyError if absent, FileNotFoundError if a table kind or a verb it
    names has no file."""
    root = bench_dir.parent
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic_file = bench_dir / "traffic" / f"{cell['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    verbs = {}
    for inst in traffic["instances"]:
        if "verb" not in inst:
            raise KeyError(f"instance {inst['name']!r} of {traffic_file} "
                           "names no verb")
        verb = inst["verb"]
        verbs[verb] = module(bench_dir / "verbs" / f"{verb}.py")
    if "table" not in config:
        raise KeyError(f"configuration {config['name']!r} names no table "
                       "kind")
    return Spec(root=root, bench_dir=bench_dir, workload=cell,
                config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)],
                table_kind=module(
                    bench_dir / "tables" / f"{config['table']}.py"),
                verbs=verbs)


def reader(bench_dir: Path, name: str):
    """`read(run)` of metrics/<name>.py."""
    return module(bench_dir / "metrics" / f"{name}.py").read
