"""The program's own `shipped` count per query (PipelineResult
.shipped_bytes): the payload it bills. The gap to resp_B_per_q is what
the wire carries beyond it, such as the zeroed columns of a projection."""


def read(run):
    if not run.queries:
        return None
    return sum(q.shipped for q in run.queries) / len(run.queries)
