"""The select_aggregate kernel's share of its roofline, in percent: the
least time its scans need (each query's verb's `kernel_bytes`: the
referenced columns' words read once, over the chip's peak HBM bandwidth,
fvb/peaks.py) over the kernel's device time.

The kernel's time is the traced window's Pallas kernel time (`kernel_s`
of the fold, every op the name table fvb/kernels.json matches): read
only where select_aggregate is the one kernel among the device's ten
busiest op names and the queries' verbs count their scan's bytes, so
that the time is this kernel's alone. Nothing to read, None."""
import re

from fvb import peaks

KERNEL = re.compile(r"^%select_aggregate(_cols)?\.\d+ custom-call$")


def read(run):
    f = run.fold
    if f is None or not f.n_chips or f.kernel_s <= 0 or not run.queries:
        return None
    kernels = [n for n, _ in f.top_ops if n.endswith(" custom-call")]
    if not kernels or not all(KERNEL.match(n) for n in kernels):
        return None
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    wb = run.spec.config["word_bytes"]
    need = 0
    for q in run.queries:
        inst = run.insts[q.inst]
        if not hasattr(inst.verb, "kernel_bytes"):
            return None
        need += inst.verb.kernel_bytes(inst.spec, wb, run.n_rows)
    return 100.0 * (need / bw) / f.kernel_s
