#!/usr/bin/env python3
"""The control of the check: the plain reference computed in bfloat16, put
in the program's place, at the cell's own size. Every compared number it
gives is printed beside its limit; the check is sound only if the control
fails (some number over its limit) on every seed.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 [--rehearse]

Numpy only: it needs no chip and does not start the program. The runs of
the benchmark never run it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from fvb import harness  # noqa: E402
from fvb import spec as fspec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    spec = fspec.load(args.workload)
    rows = harness.REHEARSAL_ROWS if args.rehearse else int(
        spec.config["rows"])
    limits = spec.limits
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        table = spec.table_kind.make_table(spec.config, seed, rows)
        nums: dict = {}
        for inst in spec.traffic["instances"]:
            verb = spec.verbs[inst["verb"]]
            want = verb.expect(table.words, table.index, inst)
            got = verb.control(table.words, table.index, inst)
            for k, v in verb.compare(got, want).items():
                nums[k] = nums.get(k, 0) + v
        fails = any(v > limits[k] for k, v in nums.items())
        failed_all &= fails
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "rows": rows, "control_fails": fails,
                          "numbers": {k: {"value": v,
                                          "limit": limits[k]}
                                      for k, v in nums.items()},
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
