"""Active streams over ``max_batch`` per round (``RoundRecord``), as a
percentage, averaged over the window's rounds."""

import numpy as np

LAYER = "cell + scheduler"
SOURCE = "program_counter"
MOVES = "goodput_tok_s"


def read(run):
    fills = [s.record.batch_occupancy for s in run.window_steps()
             if s.record.batch_occupancy is not None]
    return 100.0 * float(np.mean(fills)) if fills else None
