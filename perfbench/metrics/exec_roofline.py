"""The fused executable's share of its roofline, in percent: the least
time its queries need (the bytes their semantics require, fvb/peaks.py,
over the chip's peak HBM bandwidth) over its measured device time."""
from fvb import peaks


def read(run):
    f = run.fold
    if f is None or not f.n_chips or f.exec_s <= 0 or not run.queries:
        return None
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    wb = run.spec.config["word_bytes"]
    need = 0
    for q in run.queries:
        spec = run.insts[q.inst]
        grouped = "group" in spec
        need += peaks.query_bytes(
            spec, wb, run.n_rows, run.width,
            count=0 if grouped else q.count,
            n_overflow=q.count if grouped else 0)
    return 100.0 * (need / bw) / f.exec_s
