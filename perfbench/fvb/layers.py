"""The served path's own spans and device scopes, folded per layer.

The program marks the layers of its request path itself. Host spans are
`jax.profiler.TraceAnnotation`s:

  fv.d2h       PipelineResult.finalize: the result copied off the device
  fv.layout    finalize: a column-major result laid out as rows
  srv.encode   FViewServer._send: a frame encoded (values, CRC)
  fv.recv      RemoteNodeHandle._recv_frame: a frame's body and trailer read
  fv.crc       the same: the frame's CRC checked
  fv.decode    the same: the frame's values decoded
  fv.attach    RemotePending._attach: the zero tail restored, the result
               rebuilt

Device scopes are `jax.named_scope`s (fv.stitch, fv.bucket_sort,
fv.ovf_pack). They reach a TPU trace as the `tf_op` stat of each op's
metadata, the op's name path; `jax.profiler.ProfileData` does not show
metadata stats, so the device planes are read here from the XSpace
protobuf itself. Ops nest (a `while` runs its body's fusions), so a
scope's time is the union of its ops' intervals. In a v5e trace a
`while` op itself carries no path; the ops of its body do.

Each quantity is clipped to the measured window (`bench.window`) and,
for host spans, summed over every thread. A query is one `fv.finalize`
span (the benchmark's own) that starts in the window.

    python3 perfbench/fvb/layers.py <trace dir or .xplane.pb>

folds a trace kept by `run.py --trace 1 --trace-dir <dir>` and prints
one JSON object: the window, the queries, each span's and scope's
seconds, and the per-query milliseconds of `LAYERS`.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from fvb import tracefold  # noqa: E402

# per-query quantity -> (the spans it sums, the device scopes it unions)
LAYERS = {
    "finalize_ms_per_q": (("fv.d2h", "fv.layout"), ()),
    "encode_ms_per_q": (("srv.encode",), ()),
    "socket_ms_per_q": (("fv.recv",), ()),
    "decode_ms_per_q": (("fv.crc", "fv.decode", "fv.attach"), ()),
    "stitch_ms_per_q": ((), ("fv.stitch",)),
    "group_sort_ms_per_q": ((), ("fv.bucket_sort", "fv.ovf_pack")),
}
QUERY_SPAN = "fv.finalize"
PATH_STAT = "tf_op"


# ------------------------------------------------- the XSpace wire format
def _varint(b: bytes, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) range for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i: i + n], i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, v


def _str(b: bytes, r: tuple) -> str:
    return b[r[0]: r[1]].decode("utf-8", "replace")


def _stat(b: bytes, r: tuple, names: dict) -> tuple:
    """(stat name, its value as a string where it is one) of an XStat."""
    mid, val = 0, None
    for num, v in _fields(b, *r):
        if num == 1:
            mid = v
        elif num == 5:                  # str_value
            val = _str(b, v)
        elif num == 7:                  # ref_value: a stat metadata's name
            val = names.get(v)
    return names.get(mid), val


def _path_of(b: bytes, stats: list, names: dict) -> str | None:
    for r in stats:
        name, val = _stat(b, r, names)
        if name == PATH_STAT and val is not None:
            return val
    return None


def scoped_ops(path) -> dict:
    """Per device plane, the op events of its `XLA Ops` line as
    (start ns, end ns, name path), the path from the `tf_op` stat of the
    event or of its metadata ('' where neither has one)."""
    b = Path(tracefold.find_xplane(path)).read_bytes()
    out: dict = {}
    for num, plane in _fields(b, 0, len(b)):
        if num != 1:
            continue
        name, lines, ev_md, st_md = "", [], [], {}
        for pn, v in _fields(b, *plane):
            if pn == 2:
                name = _str(b, v)
            elif pn == 3:
                lines.append(v)
            elif pn == 4:
                ev_md.append(v)
            elif pn == 5:               # map<int64, XStatMetadata>
                key, sname = 0, ""
                for en, ev in _fields(b, *v):
                    if en == 1:
                        key = ev
                    elif en == 2:
                        sname = next((_str(b, x) for n, x in _fields(b, *ev)
                                      if n == 2), "")
                st_md[key] = sname
        if not tracefold.DEVICE_PLANE.match(name):
            continue
        paths = {}                      # event metadata id -> name path
        for r in ev_md:                 # map<int64, XEventMetadata>
            key, stats = 0, []
            for en, ev in _fields(b, *r):
                if en == 1:
                    key = ev
                elif en == 2:
                    stats = [x for n, x in _fields(b, *ev) if n == 5]
            paths[key] = _path_of(b, stats, st_md)
        ops = []
        for r in lines:
            lname, ts, events = "", 0, []
            for ln, v in _fields(b, *r):
                if ln == 2:
                    lname = _str(b, v)
                elif ln == 3:
                    ts = v
                elif ln == 4:
                    events.append(v)
            if lname != tracefold.OPS_LINE:
                continue
            for r_ev in events:
                mid = off = dur = 0
                stats = []
                for en, v in _fields(b, *r_ev):
                    if en == 1:
                        mid = v
                    elif en == 2:
                        off = v
                    elif en == 3:
                        dur = v
                    elif en == 4:
                        stats.append(v)
                start = ts + off / 1e3
                p = _path_of(b, stats, st_md) or paths.get(mid) or ""
                ops.append((start, start + dur / 1e3, p))
        out[name] = ops
    return out


# ------------------------------------------------------------- the fold
def span_seconds(spans, lo: float, hi: float) -> dict:
    """Per span name, its spans' summed duration inside [lo, hi), over
    every thread."""
    out: dict = defaultdict(float)
    for sp in spans:
        if sp.name != tracefold.WINDOW_SPAN:
            inside = min(sp.end, hi) - max(sp.start, lo)
            out[sp.name] += max(0.0, inside) / 1e9
    return dict(out)


def _in_scope(path: str, scope: str) -> bool:
    return re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", path) is not None


def scope_seconds(ops: dict, scopes, lo: float, hi: float) -> float:
    """Device time under any of `scopes` inside [lo, hi): the union of the
    scoped ops' intervals per chip, summed over chips."""
    total = 0.0
    for evs in ops.values():
        busy = tracefold.union((s, e) for s, e, p in evs
                               if any(_in_scope(p, sc) for sc in scopes))
        total += sum(e - s for s, e in tracefold.clip(busy, lo, hi))
    return total / 1e9


def fold(path) -> dict:
    """The layers of one kept trace (see the module's docstring)."""
    tr = tracefold.load(path)
    ops = scoped_ops(path)
    win = [s for s in tr.spans if s.name == tracefold.WINDOW_SPAN]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        evs = [e for p in ops.values() for e in p]
        lo = min((s for s, _, _ in evs), default=0.0)
        hi = max((e for _, e, _ in evs), default=0.0)
    queries = sum(1 for s in tr.spans
                  if s.name == QUERY_SPAN and lo <= s.start < hi)
    spans = span_seconds(tr.spans, lo, hi)
    n_spans = sum(1 for s in tr.spans if lo <= s.start < hi
                  and s.name != tracefold.WINDOW_SPAN)
    scoped = sorted({sc for _, scs in LAYERS.values() for sc in scs})
    scope_s = {sc: scope_seconds(ops, (sc,), lo, hi) for sc in scoped}
    per_q = {}
    for metric, (names, scs) in LAYERS.items():
        secs = (scope_seconds(ops, scs, lo, hi) if scs
                else sum(spans.get(n, 0.0) for n in names))
        if queries and secs > 0:        # nothing to read: left out
            per_q[metric] = secs / queries * 1e3
    return {"window_s": (hi - lo) / 1e9, "queries": queries,
            "spans_per_query": n_spans / queries if queries else None,
            "span_s": spans, "scope_s": scope_s,
            "op_paths": any(p for evs in ops.values() for _, _, p in evs),
            "per_query_ms": per_q}


def main(argv) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(fold(Path(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
