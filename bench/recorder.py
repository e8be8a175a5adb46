"""What a run records while the window runs, without adding device work.

``Recorder`` wraps three calls of the cell under test by replacing them on
their instances: ``MultiSpinCell.step`` (host time of every round),
``SpecEngine.spin_round`` (the round's key, planned lengths, frozen rows,
each live row's request and position, and the small device arrays the round
returned: draft tokens, the draft's top-vhat distributions, accept counts,
output tokens) and ``SpecEngine.add_streams`` (prefill shapes).  Nothing is
fetched from the device while the window runs: the arrays are only
referenced, and fetched once the window has closed.  With ``annotate`` the
three calls also open ``jax.profiler.TraceAnnotation`` spans, so that a
profiler trace can say what the host did in each idle gap of the device.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class Round:
    t0: float
    t1: float
    key: object                 # the round's PRNG key (device array)
    lengths: np.ndarray         # (B,) planned draft lengths
    freeze: np.ndarray          # (B,) rows that commit nothing
    J: int
    live: dict                  # row -> (rid, index of the pending token)
    out: dict                   # device arrays the round returned


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    record: object = None       # the cell's RoundRecord (None when idle)


class Recorder:
    def __init__(self, served, annotate: bool = False):
        self.rounds: list[Round] = []
        self.steps: list[Step] = []
        self.prefills: list[tuple] = []     # (t0, t1, n, M)
        eng, cell, backend = served.engine, served.cell, served.backend
        ann = (jax.profiler.TraceAnnotation if annotate
               else _NullAnnotation)
        spin, step, add = eng.spin_round, cell.step, eng.add_streams

        def spin_round(state, lengths, key, vhat=64, freeze=None,
                       draft_width=1, tree=None):
            frz = (np.zeros(len(lengths), bool) if freeze is None
                   else np.asarray(freeze, bool).copy())
            row_of = {row: rid for rid, row in backend._row_of.items()}
            live = {int(b): (int(row_of[b]), len(state.committed[b]) - 1)
                    for b in range(len(lengths))
                    if not frz[b] and b in row_of}
            t0 = time.perf_counter()
            with ann("engine.spin_round"):
                out = spin(state, lengths, key, vhat=vhat, freeze=freeze,
                           draft_width=draft_width, tree=tree)
            t1 = time.perf_counter()
            _, res, draft = out
            keep = {"tokens": draft.tokens, "q_idx": draft.q_idx,
                    "q_val": draft.q_val,
                    "accept_counts": res.accept_counts,
                    "output_tokens": res.output_tokens}
            if getattr(res, "winner", None) is not None:
                keep["winner"] = res.winner
            self.rounds.append(Round(t0, t1, key, np.asarray(lengths).copy(),
                                     frz, int(draft_width), live, keep))
            return out

        def cell_step(key=None):
            t0 = time.perf_counter()
            with ann("cell.step"):
                rec = step(key)
            self.steps.append(Step(t0, time.perf_counter(), rec))
            return rec

        def add_streams(state, prompts):
            t0 = time.perf_counter()
            with ann("engine.add_streams"):
                out = add(state, prompts)
            n, M = prompts.shape
            self.prefills.append((t0, time.perf_counter(), int(n), int(M)))
            return out

        eng.spin_round = spin_round
        cell.step = cell_step
        eng.add_streams = add_streams

    def fetch(self) -> None:
        """Bring every recorded round's arrays to the host (after the
        window), and drop the device references."""
        for r in self.rounds:
            r.key = np.asarray(jax.device_get(r.key))
            r.out = {k: np.asarray(v) for k, v in jax.device_get(r.out).items()}


class _NullAnnotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
