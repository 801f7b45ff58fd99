"""Bytes the client's connections to the server received per query,
counted at its socket objects (fvb/served.py): every frame, header and
trailer included, whatever part of the program reads them."""


def read(run):
    if not run.queries:
        return None
    return sum(q.resp_bytes for q in run.queries) / len(run.queries)
