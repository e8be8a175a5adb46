"""Reduction of a ``jax.profiler`` trace to the numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData`` alone.  On a TPU each chip is a
plane ``/device:TPU:<n>`` with a line of XLA programs ("XLA Modules") and a
line of their operations ("XLA Ops"); the host's plane carries the
``TraceAnnotation`` spans the recorder opens around the cell's calls, on
the same clock.  The traced window is the host span ``bench.window``.

    busy_s      union of the operation intervals inside the window,
                averaged over the chips in the trace;
    modules     device seconds per XLA program name (``jit_<fn>`` with the
                trailing id dropped);
    ops         device seconds per operation name (the HLO instruction's
                own name, trailing ``.<n>`` dropped; loops and calls are left
                out, their bodies' operations count);
    gaps        each idle interval of the device, named by the innermost
                host span open at its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
HOST_SPANS = ("cell.step", "engine.spin_round", "engine.add_streams")
OUTSIDE = "host outside cell.step (gateway, SSE, load generator)"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: dict            # name -> seconds
    ops: dict                # name -> seconds
    gaps: dict               # host activity -> idle seconds
    n_chips: int

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}

    def module_seconds(self, *names: str) -> float:
        return sum(s for m, s in self.modules.items()
                   if any(m == n or m == f"jit_{n}" for n in names))

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for op, s in self.ops.items() if rx.search(op))


def _union(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _complement(intervals, lo, hi):
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


CONTAINERS = ("while", "conditional", "call")


def _strip(name: str) -> str:
    """Short name of a program or operation: an operation event carries its
    whole HLO instruction (``%name.7 = type op(operands)``), of which only
    the instruction's own name counts; trailing ids are dropped."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"\.\d+$", "", name)


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def reduce(trace_dir: str) -> Summary:
    return reduce_profile(load(trace_dir))


def to_json(prof, lo_ns: int, hi_ns: int) -> dict:
    """The planes, lines and events of a trace that overlap [lo, hi], in a
    plain form ``from_json`` reads back (a recorded sample)."""
    return {"planes": [
        {"name": p.name, "lines": [
            {"name": ln.name,
             "events": [[e.name, e.start_ns, e.end_ns] for e in ln.events
                        if e.end_ns > lo_ns and e.start_ns < hi_ns]}
            for ln in p.lines]}
        for p in prof.planes
        if p.name.startswith(("/device:TPU:", "/host:"))]}


def from_json(d: dict):
    from types import SimpleNamespace as NS

    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=n, start_ns=s, end_ns=e)
                                    for n, s, e in ln["events"]])
        for ln in p["lines"]]) for p in d["planes"]])


def reduce_profile(prof) -> Summary:
    window, spans, chips = None, [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            chips.append((ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name in HOST_SPANS:
                        spans.append((e.start_ns, e.end_ns, e.name))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = window
    clip = lambda evs: [(max(s, lo), min(e, hi), n)  # noqa: E731
                        for s, e, n in evs if e > lo and s < hi]
    busy, modules, opsec, gaps = 0.0, {}, {}, {}
    s0 = np.asarray([s for s, _, _ in spans], np.int64)
    s1 = np.asarray([e for _, e, _ in spans], np.int64)
    names = [n for _, _, n in spans]
    for ops, mods in chips:
        ops, mods = clip(ops), clip(mods)
        busy += _union([(s, e) for s, e, _ in ops]) / len(chips)
        for s, e, n in mods:
            k = _strip(n)
            modules[k] = modules.get(k, 0.0) + (e - s) * 1e-9 / len(chips)
        for s, e, n in ops:
            k = _strip(n)
            if k in CONTAINERS:     # their bodies' operations are counted
                continue
            opsec[k] = opsec.get(k, 0.0) + (e - s) * 1e-9 / len(chips)
        idle = np.asarray(_complement([(s, e) for s, e, _ in ops], lo, hi),
                          np.int64).reshape(-1, 2)
        for i in range(0, len(idle), 4096):
            chunk = idle[i:i + 4096]
            mid = (chunk[:, :1] + chunk[:, 1:]) // 2
            inside = (s0[None, :] <= mid) & (mid < s1[None, :])
            dur = np.where(inside, (s1 - s0)[None, :], np.iinfo(np.int64).max)
            inner = np.argmin(dur, axis=1) if len(spans) else None
            for (a, b), hit, k in zip(chunk, inside.any(axis=1),
                                      inner if inner is not None
                                      else [0] * len(chunk)):
                name = names[k] if hit else OUTSIDE
                gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9 / len(chips)
    gaps = {k: float(v) for k, v in gaps.items()}
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   modules=modules, ops=opsec,
                   gaps=gaps, n_chips=len(chips))
