"""Host time of the cell's rounds (``MultiSpinCell.step``, admission and
prefill included) over the number of rounds, in the window."""

LAYER = "backend + engine"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"


def read(run):
    steps = run.window_steps()
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps) if steps else None
