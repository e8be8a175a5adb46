"""Committed tokens streamed to clients inside the window, over the
window's seconds: the paper's sum token goodput on the wall clock."""

LAYER = "gateway"
SOURCE = "host_clock"
MOVES = None


def read(run):
    tokens = sum(n for o in run.outcomes for t, n, _ in o.events
                 if run.t_open <= t < run.t_close)
    return tokens / run.seconds
