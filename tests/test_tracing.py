"""The request path's own profiler marks: host spans (`TraceAnnotation`)
at its layer boundaries and device scopes (`jax.named_scope`) on its
largest device stretches.

Host spans: `fv.d2h` and `fv.layout` in `PipelineResult.finalize`,
`srv.encode` in the server's frame send, `fv.recv`, `fv.crc`, `fv.decode`
in the client's frame read, `fv.attach` where the client rebuilds a
result. Device scopes: `fv.bucket_sort` (the group kernel's bucket sort
and ownership, and the stream put in bucket order), `fv.ovf_pack` (the
group's overflow compaction), `fv.agg` (the scalar aggregate's kernel
and the sum of its lanes); a select carries none, its compaction being
all in the select kernel. Each is checked where it lands: the
spans in a profiler trace, the scopes in the compiled program's op
names, which a device trace carries as each op's name path.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import operators as op
from repro.core import client as fv
from repro.core.pipeline import CompiledPipeline
from repro.core.table import Column, FTable
from repro.net import RemoteNodeHandle
from repro.net.server import FViewServer

N = 600
COLS = tuple(Column(f"c{i}", "i32" if i == 0 else "f32") for i in range(8))
SEL = op.Select((op.Predicate("c2", "<", 0.3),))
GROUP = (SEL, op.GroupBy("c0", ("c1", "c3"), n_buckets=64))
AGG = (op.Select((op.Predicate("c2", ">=", -0.5),
                  op.Predicate("c2", "<", 0.3))),
       op.Aggregate((("c0", "c0"), ("c0",))))


def _rows(seed=0):
    rng = np.random.default_rng(seed)
    data = {f"c{i}": rng.normal(size=N).astype(np.float32)
            for i in range(1, 8)}
    data["c0"] = rng.integers(0, 90, N).astype(np.int32)
    return FTable("t", COLS, n_rows=N).encode(data)


def _spans(tmp_path, fn) -> dict:
    """Run `fn` under the profiler; each span name's list of durations."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    out: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("fv.", "srv.")):
                        out.setdefault(e.name, []).append(e.duration_ns)
    return out


@pytest.mark.parametrize("pipeline, interpret, scopes", [
    ((op.Project(("c1", "c4")), SEL), False, ()),
    (GROUP, False, ("fv.bucket_sort", "fv.ovf_pack")),
    (GROUP, True, ("fv.ovf_pack",)),
    (AGG, False, ("fv.agg",)),
], ids=["select-kernels", "group-kernels", "group-xla", "aggregate-kernels"])
def test_device_scopes_name_the_compiled_ops(pipeline, interpret, scopes):
    pipe = CompiledPipeline(FTable("t", COLS, n_rows=N), pipeline,
                            interpret=interpret)
    text = pipe._jit_rows.lower(jnp.asarray(_rows()), None, None,
                                None).compile().as_text()
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    # no path carries a scope it does not name (a select none: its
    # compaction is all in the select kernel)
    for scope in ({"fv.stitch", "fv.bucket_sort", "fv.ovf_pack", "fv.agg"}
                  - set(scopes)):
        assert f"/{scope}/" not in text, scope


def test_finalize_spans_the_copy_and_the_row_layout(tmp_path):
    """The kernel lowering's column-major answer: the copy off the device
    and the row layout are spans of their own; a group's overflow copy
    is a `fv.d2h` too."""
    words = _rows(1)
    sel = CompiledPipeline(FTable("t", COLS, n_rows=N),
                           (op.Project(("c1", "c4")), SEL), interpret=False)
    grp = CompiledPipeline(FTable("t", COLS, n_rows=N), GROUP,
                           interpret=False)
    s_res, g_res = sel(words), grp(words)
    jax.block_until_ready((s_res._raw, g_res._raw))
    spans = _spans(tmp_path / "sel", s_res.finalize)
    assert len(spans["fv.d2h"]) == 1 and len(spans["fv.layout"]) == 1
    assert min(spans["fv.d2h"] + spans["fv.layout"]) > 0
    spans = _spans(tmp_path / "grp", g_res.finalize)
    assert len(spans["fv.d2h"]) == 1 and "fv.layout" not in spans
    # the layout is unchanged by the spans
    want = np.asarray(words)[np.asarray(words)[:, 2] < 0.3]
    assert s_res.count == len(want)
    np.testing.assert_array_equal(s_res.rows[: s_res.count, 1], want[:, 1])


def test_served_query_spans_the_wire_on_both_ends(tmp_path):
    """One select over the socket: the server encodes its frames under
    `srv.encode`; the client reads, checks and decodes each under
    `fv.recv`, `fv.crc`, `fv.decode`, and rebuilds the result under
    `fv.attach`."""
    server = FViewServer.start_in_thread(capacity_bytes=16 << 20)
    try:
        handle = RemoteNodeHandle(server.host, server.port)
        qp = fv.open_connection(handle)
        ft = fv.alloc_table_mem(qp, FTable("t", COLS, n_rows=N))
        fv.table_write(qp, ft, _rows(2))
        fv.farview_request(qp, ft, (SEL,)).finalize()      # warm

        def query():
            fv.farview_request(qp, ft, (SEL,)).finalize()
        spans = _spans(tmp_path, query)
        handle.close()
    finally:
        server.stop_thread()
    # RESULT and the FLUSH's OK: two frames each way at least
    for name in ("srv.encode", "fv.recv", "fv.crc", "fv.decode"):
        assert len(spans.get(name, ())) >= 2, name
    assert len(spans["fv.attach"]) == 1
    for name in ("srv.encode", "fv.recv", "fv.crc", "fv.decode",
                 "fv.attach"):
        assert min(spans[name]) > 0, name
