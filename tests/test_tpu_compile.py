"""Compile rehearsals for the TPU: the main path's Pallas kernels and the
fused request executables, compiled by the TPU compiler for a described
(not attached) v5e chip at benchmark widths (8-word rows).

Nothing runs; each test asserts the compiler accepted the program and
that it holds a Mosaic kernel (`tpu_custom_call`), so a kernel the chip's
compiler would refuse (tiling, an unlowered primitive, too much VMEM) or
a fused executable that silently fell back to XLA fails here, at no chip
time. The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and pytest-xdist imports this
file in every worker.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import operators as op
from repro.core.pipeline import CompiledPipeline
from repro.core.table import Column, FTable
from repro.kernels import ops as kops

N = 1 << 13                     # rows: enough for several blocks per grid
W = 8                           # the paper's 8-attribute rows
PAGE_WORDS = 1 << 19            # 2 MiB pool pages
POOL_PAGES = 64
SEL = op.Select((op.Predicate("c4", "<", 0.1),))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def native(monkeypatch):
    """The kernels as they lower on a TPU (this process's backend is the
    CPU, where they would pick interpret mode)."""
    monkeypatch.setattr(kops, "_interpret_default", lambda: False)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    "select_project": (
        lambda t, o, v, p: kops.select_project_cols(t, o, v, p),
        ((W + 1, N), jnp.float32), ((W + 1,), jnp.int32),
        ((W + 1,), jnp.float32), ((W + 1,), jnp.float32)),
    "group_aggregate": (
        lambda k, v: kops.group_aggregate_cols(k, v, n_buckets=1024),
        ((N,), jnp.int32), ((3, N), jnp.float32)),
    "regex_match": (
        lambda s, ln, t, a: kops.regex_match(s, ln, t, a),
        ((N, 32), jnp.uint8), ((N,), jnp.int32), ((13, 256), jnp.int32),
        ((13,), jnp.bool_)),
    "hash_join": (
        lambda p, k, v: kops.hash_join_cols(p, k, v),
        ((N,), jnp.int32), ((50,), jnp.int32), ((50, 2), jnp.float32)),
    "crypt": (
        lambda d: kops.crypt(d, np.array([21, 42], np.uint32), 99),
        ((N * W,), jnp.uint32)),
    "crypt_cols": (
        lambda d: kops.crypt_cols(d, np.array([21, 42], np.uint32), 99),
        ((W, N), jnp.uint32)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, native, name):
    fn, *shapes = KERNELS[name]
    _compile(jax.jit(fn), *(_spec(one_chip, s, d) for s, d in shapes))


PIPES = {
    "select": (op.Project(("c0", "c4", "c5")), SEL),
    "smart_address": (op.SmartAddress(("c4", "c5")), SEL),
    "pre_decrypt": (op.Crypt(key=(21, 42), nonce=99, when="pre"), SEL),
    "group": (SEL, op.GroupBy("c0", ("c1", "c2"), n_buckets=1024)),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["solo", "stacked"])
@pytest.mark.parametrize("name", sorted(PIPES))
def test_fused_executable_compiles_for_v5e(one_chip, native, name, stacked):
    """`_jit_pages` on the kernel lowering: one request, or a stacked
    4-client round, straight from the pool buffer."""
    schema = FTable("t", (Column("c0", "i32"),)
                    + tuple(Column(f"c{i}") for i in range(1, W)), n_rows=N)
    pipe = CompiledPipeline(schema, PIPES[name], interpret=False)
    n_pages = -(-N * W // PAGE_WORDS)
    lead = (4,) if stacked else ()
    _compile(pipe._jit_pages,
             _spec(one_chip, (POOL_PAGES + 1, PAGE_WORDS), jnp.float32),
             _spec(one_chip, lead + (n_pages,), jnp.int32),
             _spec(one_chip, lead, jnp.int32), None, None, None,
             n_rows=N, row_words=W, page_words=None)
