"""The one generator of traffic: a mix file's instances, as the program's
operator pipelines, in an order drawn from the seed.

An instance is plain data: its `name`, its `verb` (the module
`verbs/<verb>.py`, which reads the instance's other keys) and those keys.
A configuration held encrypted at rest puts `Crypt(pre)` with its key in
front of every instance. The loop is closed with one client: the next
query goes out when the previous answer is final. Every round issues each
instance once, in an order drawn from the seed, so that each instance
weighs the same in every run whatever the window holds.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import ModuleType

from fvb.data import rng_of
from fvb.spec import Spec


@dataclass(frozen=True)
class Instance:
    name: str
    spec: dict
    pipeline: tuple         # the program's operator IR
    verb: ModuleType        # verbs/<spec["verb"]>.py


def pipeline_of(spec: dict, config: dict, verb: ModuleType) -> tuple:
    from repro.core import operators as op
    ops = []
    at_rest = config.get("encrypted_at_rest")
    if at_rest:
        ops.append(op.Crypt(key=tuple(at_rest["key"]),
                            nonce=int(at_rest["nonce"]), when="pre"))
    ops.extend(verb.pipeline(spec, config))
    return op.validate_pipeline(tuple(ops))


def instances(spec: Spec) -> list[Instance]:
    out = []
    for s in spec.traffic["instances"]:
        verb = spec.verbs[s["verb"]]
        out.append(Instance(s["name"], s, pipeline_of(s, spec.config, verb),
                            verb))
    return out


def rounds(insts: list[Instance], seed: int):
    """Endless rounds: each a seeded permutation of the instances."""
    rng = rng_of(seed, stream=1)
    while True:
        yield [insts[int(i)] for i in rng.permutation(len(insts))]
