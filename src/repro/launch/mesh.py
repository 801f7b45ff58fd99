"""Production meshes. Importing this module never touches jax device state.

Single pod:  (16, 16)    axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

The "model" axis carries tensor parallelism, expert parallelism, and the
Farview disaggregated-pool striping (far-KV sequence shards). The "data"
(+"pod") axes carry batch data parallelism and ZeRO/FSDP parameter sharding.
Cross-pod traffic (DCN) only ever sees data-parallel gradient reductions —
which is what the int8+error-feedback compressor (distributed/compress.py)
targets.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

set_mesh = jax.set_mesh         # re-export: callers install a mesh from here


def make_mesh(shape, axes):
    """`jax.make_mesh` with Auto axis types: the compiler propagates the
    sharding, which every sharded entry point in this repo expects."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (device count must already allow it)."""
    return make_mesh(shape, axes)


# TPU v5e hardware constants used by the roofline analysis (per chip).
HW = {
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw_per_link": 50e9,       # B/s per link (~2 links usable per axis)
    "dcn_bw": 25e9,                # B/s per host across pods (approximate)
    "hbm_bytes": 16 * 2**30,       # 16 GiB HBM per chip
}
