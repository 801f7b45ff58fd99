"""The fused pipeline's kernel lowering (`interpret=False`) against its
XLA-native lowering, on the same pool pages.

The kernel lowering is what runs on the TPU: Pallas kernels over a
column-major (A, n) work table. Off the TPU its kernels run in Pallas
interpret mode, so every verb's traced program — column reads, the
column-major cipher, the join/id/validity columns, the select kernel's
global compaction and the host-side row layout of the response — is
checked here against the XLA-native lowering, which
tests/test_fused_path.py holds to kernels/ref.py.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import operators as op
from repro.core.pipeline import CompiledPipeline
from repro.core.pool import FarPool
from repro.core.table import Column, FTable
from repro.kernels import ref

N = 600            # not a multiple of any block: exercises every tail pad
KEY, NONCE = (7, 13), 21
SEL = op.Select((op.Predicate("c2", "<", 0.3), op.Predicate("c5", ">", -0.8)))

CASES = {
    "select_project": (op.Project(("c1", "c4")), SEL),
    "smart_address": (op.SmartAddress(("c2", "c5", "c6")), SEL),
    "pre_decrypt": (op.Crypt(key=KEY, nonce=NONCE, when="pre"), SEL),
    "post_encrypt": (SEL, op.Crypt(key=(9, 9), nonce=3, when="post")),
    "group_by": (SEL, op.GroupBy("c0", ("c1", "c3"), n_buckets=64)),
    "distinct": (op.Distinct(("c0",), n_buckets=32),),
    "join": (SEL, op.JoinSmall("c0", "b", "k", ("v0", "v1"))),
}


def _table(pool, rng, encrypt):
    cols = tuple(Column(f"c{i}", "i32" if i == 0 else "f32")
                 for i in range(8))
    ft = pool.alloc_table(FTable("t", cols, n_rows=N))
    data = {f"c{i}": rng.normal(size=N).astype(np.float32)
            for i in range(1, 8)}
    data["c0"] = rng.integers(0, 90, N).astype(np.int32)
    words = ft.encode(data)
    stored = words
    if encrypt:
        u32 = words.reshape(-1).view(np.uint32)
        enc = ref.ctr_crypt(jnp.asarray(u32), jnp.asarray(KEY, jnp.uint32),
                            NONCE)
        stored = np.asarray(enc).view(np.float32).reshape(words.shape)
    pool.write_table(ft, stored)
    return ft


def _same(a, b, name):
    """Field-for-field equality of two finalized results."""
    assert a.kind == b.kind, name
    if a.kind == "rows":
        assert a.count == b.count, name
        np.testing.assert_array_equal(np.asarray(a.rows), np.asarray(b.rows))
        assert a.shipped_bytes == b.shipped_bytes
        if a.sel_ids is not None or b.sel_ids is not None:
            np.testing.assert_array_equal(a.sel_ids, b.sel_ids)
        return
    ga, gb = a.groups, b.groups
    claimed = np.asarray(gb["count"]) > 0
    for k in ("bucket_keys", "count"):
        np.testing.assert_array_equal(np.asarray(ga[k]), np.asarray(gb[k]))
    np.testing.assert_allclose(np.asarray(ga["sum"]), np.asarray(gb["sum"]),
                               rtol=1e-5, atol=1e-5)
    for k in ("min", "max"):
        np.testing.assert_array_equal(np.asarray(ga[k])[claimed],
                                      np.asarray(gb[k])[claimed])
    np.testing.assert_array_equal(ga["ovf_keys"], gb["ovf_keys"])
    np.testing.assert_array_equal(ga["ovf_vals"], gb["ovf_vals"])
    assert a.shipped_bytes == b.shipped_bytes


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_lowering_matches_xla(case):
    """Solo, partitioned (row ids) and stacked dispatch of every verb."""
    rng = np.random.default_rng(len(case))
    pool = FarPool(64 * 4096, page_bytes=4096)
    ft = _table(pool, rng, encrypt=case == "pre_decrypt")
    pipe = CASES[case]
    build = None
    if case == "join":
        bk = rng.permutation(90)[:40].astype(np.int32)
        build = (bk, rng.normal(size=(40, 2)).astype(np.float32))
    kern = CompiledPipeline(ft, pipe, interpret=False)
    xla = CompiledPipeline(ft, pipe, interpret=True)
    assert kern._cm and not xla._cm
    shape = dict(n_rows=N, row_words=8)
    pages = np.asarray(ft.pages, np.int32)

    _same(kern.run_pages(pool.buf, pages, N, build, **shape).finalize(),
          xla.run_pages(pool.buf, pages, N, build, **shape).finalize(), case)

    ids = rng.permutation(4 * N)[:N].astype(np.int32)
    if kern.kind == "rows":
        _same(kern.run_pages(pool.buf, pages, N, build, row_ids=ids,
                             **shape).finalize(),
              xla.run_pages(pool.buf, pages, N, build, row_ids=ids,
                            **shape).finalize(), case + "/ids")

    # a stacked round: the second request's tail is masked by n_valid
    stack = np.stack([pages, pages])
    nv = np.asarray([N, N - 77], np.int32)
    for a, b in zip(kern.run_pages_batched(pool.buf, stack, nv, build,
                                           **shape),
                    xla.run_pages_batched(pool.buf, stack, nv, build,
                                          **shape)):
        _same(a.finalize(), b.finalize(), case + "/stacked")
