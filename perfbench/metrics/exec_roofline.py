"""The fused executable's share of its roofline, in percent: the least
time its queries need (the bytes their semantics require, each query's
verb's `query_bytes`, over the chip's peak HBM bandwidth, fvb/peaks.py)
over its measured device time."""
from fvb import peaks


def read(run):
    f = run.fold
    if f is None or not f.n_chips or f.exec_s <= 0 or not run.queries:
        return None
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    wb = run.spec.config["word_bytes"]
    need = 0
    for q in run.queries:
        inst = run.insts[q.inst]
        need += inst.verb.query_bytes(inst.spec, wb, run.n_rows, run.width,
                                      q.count)
    return 100.0 * (need / bw) / f.exec_s
