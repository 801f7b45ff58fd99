"""The one generator of traffic: a mix file's instances, as the program's
operator pipelines, in an order drawn from the seed.

An instance is plain data:

    {"name": "S25P",
     "project": [cols] | "smart": [cols],       (at most one of the two)
     "select": [[col, op, value], ...],         (AND of predicates)
     "group": {"key": col, "values": [cols], "aggs": [...], "n_buckets": n}}

A configuration held encrypted at rest puts `Crypt(pre)` with its key in
front of every instance. The loop is closed with one client: the next
query goes out when the previous answer is final. Every round issues each
instance once, in an order drawn from the seed, so that each instance
weighs the same in every run whatever the window holds.
"""
from __future__ import annotations

from dataclasses import dataclass

from fvb.data import rng_of


@dataclass(frozen=True)
class Instance:
    name: str
    spec: dict
    pipeline: tuple         # the program's operator IR

    @property
    def is_group(self) -> bool:
        return "group" in self.spec


def pipeline_of(spec: dict, config: dict) -> tuple:
    from repro.core import operators as op
    ops = []
    at_rest = config.get("encrypted_at_rest")
    if at_rest:
        ops.append(op.Crypt(key=tuple(at_rest["key"]),
                            nonce=int(at_rest["nonce"]), when="pre"))
    if "project" in spec:
        ops.append(op.Project(tuple(spec["project"])))
    if "smart" in spec:
        ops.append(op.SmartAddress(tuple(spec["smart"])))
    if spec.get("select"):
        ops.append(op.Select(tuple(op.Predicate(c, o, float(v))
                                   for c, o, v in spec["select"])))
    if "group" in spec:
        g = spec["group"]
        ops.append(op.GroupBy(g["key"], tuple(g["values"]),
                              aggs=tuple(g["aggs"]),
                              n_buckets=int(g["n_buckets"])))
    return op.validate_pipeline(tuple(ops))


def instances(traffic: dict, config: dict) -> list[Instance]:
    return [Instance(s["name"], s, pipeline_of(s, config))
            for s in traffic["instances"]]


def rounds(insts: list[Instance], seed: int):
    """Endless rounds: each a seeded permutation of the instances."""
    rng = rng_of(seed, stream=1)
    while True:
        yield [insts[int(i)] for i in rng.permutation(len(insts))]
