"""Benchmark harness: one module per paper table/figure. CSV to stdout.

  bench_rdma        Fig. 6   read throughput / response time
  bench_projection  Fig. 7   projection vs smart addressing
  bench_selection   Fig. 8   selection @ 100/50/25% selectivity
  bench_grouping    Fig. 9   distinct / group-by+sum
  bench_regex       Fig. 10  regex matching
  bench_crypto      Fig. 11  encryption on the read path
  bench_multiclient Fig. 12  6 concurrent clients (stacked dispatch)
  bench_multiclient_mixed    mixed-size/kind round: 3 stacked dispatches
                             serve 8 clients (bucketing + string/join stacks)
  bench_join        (§7 fut.) small-table in-memory join
  bench_resources   Table 1  per-operator resource budget
  bench_far_kv      (LM)     far-KV push-down economics
  bench_cluster_scaleout     mixed-workload throughput at 1/2/4 nodes
  bench_rebalance            skew-flip -> drift detect -> live migration
                             -> throughput recovery vs a fresh map
  bench_failover             kill-a-node under mixed load: byte-identical
                             failover dip -> heal -> throughput recovery
  bench_network              FViewServer fan-in: p50/p99 request latency
                             vs connection count + typed overload shedding
  bench_chaos                seeded socket faults through ChaosProxy:
                             clean/soak/degraded phases, chaos tail ratio
                             and hedged gray-failure recovery
  bench_tiering              hot/cold memory tiering: effective-capacity
                             multiplier, cold-scan byte reduction, hot-path
                             no-regression round-trip, client cache hits

FV rows time the fused jitted request path with BLOCKING p50 timing (see
common.timeit); shipped/read byte columns are exact and carry the paper's
actual claims.

`--json PATH` additionally writes `{"meta": ..., "rows": [...]}`: the rows
are structured records (bench, name, us_per_call, plus per-bench fields
like shipped_frac/rows) and the meta block stamps git commit, jax version,
device platform and quick-mode — so BENCH_*.json files form a comparable
trajectory PR over PR, e.g.:

    python -m benchmarks.run --json BENCH_$(date +%Y%m%d_%H%M%S).json

`benchmarks.check_regression` diffs two such files (CI runs it against the
latest committed BENCH_*.json and fails on a >2x p50 regression).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_chaos, bench_cluster_scaleout, bench_crypto,
                        bench_failover, bench_far_kv, bench_grouping,
                        bench_join, bench_multiclient,
                        bench_multiclient_mixed, bench_network,
                        bench_projection, bench_rdma, bench_rebalance,
                        bench_regex, bench_resources, bench_selection,
                        bench_tiering, common)
from benchmarks.common import print_csv, write_json
from repro import compile_cache

ALL = {
    "rdma": bench_rdma.run,
    "projection": bench_projection.run,
    "selection": bench_selection.run,
    "grouping": bench_grouping.run,
    "regex": bench_regex.run,
    "crypto": bench_crypto.run,
    "multiclient": bench_multiclient.run,
    "multiclient_mixed": bench_multiclient_mixed.run,
    "join": bench_join.run,
    "resources": bench_resources.run,
    "far_kv": bench_far_kv.run,
    "cluster_scaleout": bench_cluster_scaleout.run,
    "rebalance": bench_rebalance.run,
    "failover": bench_failover.run,
    "network": bench_network.run,
    "chaos": bench_chaos.run,
    "tiering": bench_tiering.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=tuple(ALL))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON record list "
                         "(e.g. BENCH_20260728_120000.json)")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode (CI): 1 warmup + 1 repeat, reduced "
                         "sizes — indicative timings, exact byte columns")
    args = ap.parse_args()
    compile_cache.enable()
    if args.quick:
        common.QUICK = True
    for name, fn in ALL.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        fn()
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    print_csv()
    if args.json:
        write_json(args.json)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
