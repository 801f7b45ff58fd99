"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it. On a TPU the trace has one plane per
chip (`/device:TPU:<i>`) with a line of XLA modules (one event per
executable run) and a line of XLA ops (one event per operation), and a
host plane (`/host:CPU`) whose thread lines hold the benchmark's own
`TraceAnnotation` spans. All share one clock.

The reduction:

  * the window is the benchmark's `bench.window` span (the measured
    window), or, without it, the extent of the device events;
  * busy is the union of the op intervals inside the window, per chip;
    idle gaps are the rest of the window, each labelled with the
    innermost benchmark span that covers most of it;
  * executable time is the summed duration of module events whose name
    holds one of `EXEC_MODULES`; kernel time is the summed duration of op
    events whose name (the HLO instruction with its operands) the name
    table `kernels.json` matches.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# the fused request executable's jitted entry (core/pipeline.py)
EXEC_MODULES = ("_pages_entry",)
KERNEL_TABLE = Path(__file__).with_name("kernels.json")


@dataclass
class Event:
    name: str
    start: float            # ns, on the trace's clock
    dur: float              # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)      # plane -> [Event]
    modules: dict = field(default_factory=dict)  # plane -> [Event]
    spans: list = field(default_factory=list)    # benchmark host spans


@dataclass
class Fold:
    window_s: float
    busy_s: float               # union of device busy, mean over chips
    exec_s: float               # fused executable module time, all chips
    kernel_s: float             # matched kernel op time, all chips
    n_chips: int
    gaps: list                  # [(label, seconds)], longest first
    top_ops: list               # [(name, seconds)], most time first


def find_xplane(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: Path) -> Trace:
    """Read the trace: device ops and modules, and the benchmark's spans
    (names starting with `bench.`, `fv.` or `srv.`)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(find_xplane(path)))
    tr = Trace()
    prefixes = ("bench.", "fv.", "srv.")
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                (tr.ops if line.name == OPS_LINE else tr.modules)[
                    plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefixes):
                        tr.spans.append(Event(e.name, e.start_ns,
                                              e.duration_ns))
    return tr


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def gaps_of(busy, lo: float, hi: float) -> list:
    """The parts of [lo, hi) that `busy` (merged) does not cover."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if t < hi:
        out.append([t, hi])
    return out


def label(gap, spans) -> str:
    """What the host was doing in the gap: each part of it goes to the
    innermost (shortest) benchmark span open then, and the span with the
    most of the gap names it; 'none' where only the window's is open."""
    inside = [sp for sp in spans if sp.name != WINDOW_SPAN
              and sp.start < gap[1] and sp.end > gap[0]]
    cuts = sorted({gap[0], gap[1]} | {t for sp in inside
                                      for t in (sp.start, sp.end)
                                      if gap[0] < t < gap[1]})
    share: dict = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [sp for sp in inside if sp.start <= mid < sp.end]
        if open_:
            share[min(open_, key=lambda sp: sp.dur).name] += b - a
    return max(share, key=share.get) if share else "none"


def short(name: str) -> str:
    """An HLO op event's name without its operands: '%fusion.30 fusion'."""
    m = re.match(r"(%\S+) = .*? ([a-z][\w-]*)\(", name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def kernel_patterns(table: Path = KERNEL_TABLE) -> list:
    doc = json.loads(Path(table).read_text())
    return [re.compile(p) for k in doc["kernels"] for p in k["match"]]


def fold(tr: Trace, patterns=None, top: int = 10) -> Fold:
    patterns = kernel_patterns() if patterns is None else patterns
    win = [s for s in tr.spans if s.name == WINDOW_SPAN]
    planes = sorted(set(tr.ops) | set(tr.modules))
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        evs = [e for p in planes for e in tr.ops.get(p, [])
               or tr.modules.get(p, [])]
        if not evs:
            return Fold(0.0, 0.0, 0.0, 0.0, 0, [], [])
        lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    busy_ns, gaps, exec_ns, kern_ns = 0.0, [], 0.0, 0.0
    per_op: dict = defaultdict(float)
    for p in planes:
        ops = tr.ops.get(p) or tr.modules.get(p, [])
        busy = clip(union((e.start, e.end) for e in ops), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        gaps += gaps_of(busy, lo, hi)
        for e in tr.modules.get(p, []):
            if any(m in e.name for m in EXEC_MODULES):
                exec_ns += _inside(e, lo, hi)
        for e in tr.ops.get(p, []):
            d = _inside(e, lo, hi)
            per_op[short(e.name)] += d
            if any(r.search(e.name) for r in patterns):
                kern_ns += d
    n = max(1, len(planes))
    gaps.sort(key=lambda g: g[0] - g[1])
    return Fold(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / n / 1e9,
        exec_s=exec_ns / 1e9, kernel_s=kern_ns / 1e9, n_chips=len(planes),
        gaps=[(label(g, tr.spans), (g[1] - g[0]) / 1e9) for g in gaps[:top]],
        top_ops=[(k, v / 1e9) for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]])


def _inside(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end, hi) - max(e.start, lo))
