"""The program's `read_bytes` per query: bytes pulled from the pool, an
exact count (the same that bytes_read_pool sums)."""


def read(run):
    if not run.queries:
        return None
    return sum(q.read for q in run.queries) / len(run.queries)
