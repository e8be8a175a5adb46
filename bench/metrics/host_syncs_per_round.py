"""Blocking device-to-host fetches per round (``RoundRecord.n_host_syncs``),
averaged over the window's rounds."""

import numpy as np

LAYER = "backend + engine"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(run):
    n = [s.record.n_host_syncs for s in run.window_steps()
         if s.record.n_host_syncs is not None]
    return float(np.mean(n)) if n else None
