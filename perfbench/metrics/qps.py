"""Queries completed per second: every answer of the window over the
window's whole span, from the first send to the last finalized answer."""


def read(run):
    if not run.queries or run.span_s <= 0:
        return None
    return len(run.queries) / run.span_s
