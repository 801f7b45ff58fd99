"""What every verb's plain reference shares: the predicates and the
rounding of the control.

A verb's reference (`verbs/<verb>.py`: `expect`, `compare`, `control`) is
numpy over the plaintext words the benchmark made; nothing of the program
is imported and nothing it made is read. A predicate holds where the f32
value compares with the f32 constant; an instance's `select` is the AND of
its predicates.

The control is the reference computed in bfloat16, the precision below
the configurations' float32 words: the words rounded to bfloat16 before
the predicates and in the answer, and each aggregate rounded to bfloat16
(the best a bfloat16 accumulation could give). It must fail.
"""
from __future__ import annotations

import numpy as np

OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
       ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


def mask(words: np.ndarray, index, spec: dict) -> np.ndarray:
    """The rows where every predicate of the instance's `select` holds."""
    m = np.ones(words.shape[0], bool)
    for col, op, value in spec.get("select", ()):
        m &= OPS[op](words[:, index(col)], np.float32(value))
    return m
