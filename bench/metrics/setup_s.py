"""From process start to the opening of the window: weights from the seed,
warm-up (compiles, or loads from the compilation cache), gateway start and
the ramp-up until the batch is full."""

LAYER = "gateway"
SOURCE = "host_clock"
MOVES = None


def read(run):
    return run.t_open - run.t_start
