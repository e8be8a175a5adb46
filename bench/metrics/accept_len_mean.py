"""Tokens committed per active stream per round, bonus included
(``RoundRecord.accepted`` over ``active``), over the window's rounds."""

import numpy as np

LAYER = "cell + scheduler"
SOURCE = "program_counter"
MOVES = "goodput_tok_s"


def read(run):
    acc = act = 0
    for s in run.window_steps():
        a = np.asarray(s.record.active, bool)
        acc += int(np.asarray(s.record.accepted)[a].sum())
        act += int(a.sum())
    return acc / act if act else None
