"""Median, over every request due in the window, of the time from when the
request was due to its first token event.  A request that failed or never
streamed counts as infinitely late.  A window holds some 40 requests, so
a 95th percentile would rest on the two slowest: the median is the
reading that holds still from run to run; the note prints the p95."""

import math

import numpy as np

LAYER = "gateway"
SOURCE = "host_clock"
MOVES = None


def _waits(run):
    return [(o.first_t - o.due) if o.first_t is not None else math.inf
            for o in run.window_outcomes()]


def read(run):
    waits = _waits(run)
    return float(np.percentile(np.asarray(waits), 50)) if waits else None


def note(run):
    waits = _waits(run)
    p95 = float(np.percentile(np.asarray(waits), 95)) if waits else math.nan
    return f"{len(waits)} requests due in the window; p95 {p95!r} s"
