"""95th percentile, over every pair of consecutive token events of every
request, of the gap between them, for gaps that end in the window.  One
event is one round's commit for that stream and may carry several
tokens."""

import numpy as np

LAYER = "gateway"
SOURCE = "host_clock"
MOVES = None


def _gaps(run):
    return [b[0] - a[0] for o in run.outcomes
            for a, b in zip(o.events, o.events[1:])
            if run.t_open <= b[0] < run.t_close]


def read(run):
    gaps = _gaps(run)
    return 1e3 * float(np.percentile(np.asarray(gaps), 95)) if gaps else None


def note(run):
    return f"{len(_gaps(run))} gaps in the window"
