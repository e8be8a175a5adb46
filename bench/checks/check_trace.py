"""Check the reduction from a profiler trace to metrics.

    python3 bench/checks/check_trace.py

Two checks of ``devtrace.reduce_profile``:

1. a hand-made trace with known answers: overlapping and nested device
   operations, a window that cuts events, two chips, nested host spans;
2. the recorded sample ``trace_sample.json.gz`` (150 ms of a ``q3b.chat``
   trace on a TPU v5e, kept by ``trace_sample.py``): busy time, per-program
   and per-operation time and the idle time per host activity, each
   recomputed here independently on a 100 ns grid, must agree with the
   reduction to within the grid's rounding.
"""

import gzip
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402

SAMPLE = Path(__file__).resolve().parent / "trace_sample.json.gz"


def synthetic():
    ms = 1_000_000
    host = {"name": "/host:CPU", "lines": [{"name": "py", "events": [
        [devtrace.WINDOW, 10 * ms, 110 * ms],
        ["cell.step", 0, 60 * ms],
        ["engine.spin_round", 20 * ms, 55 * ms],
        ["engine.add_streams", 70 * ms, 90 * ms]]}]}

    def chip(i, ops):
        return {"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": [["jit_draft_step(7)", 5 * ms,
                                                40 * ms]]},
            {"name": "XLA Ops", "events": ops}]}

    ops0 = [["fusion.1", 5 * ms, 30 * ms], ["fusion.2", 25 * ms, 40 * ms],
            ["paged_attention.3", 30 * ms, 35 * ms],
            ["fusion.4", 50 * ms, 60 * ms], ["custom-call.9", 80 * ms, 85 * ms]]
    ops1 = [["fusion.1", 10 * ms, 20 * ms]]
    s = devtrace.reduce_profile(devtrace.from_json(
        {"planes": [host, chip(0, ops0), chip(1, ops1)]}))
    want = {"window_s": 0.1, "busy_s": (45e-3 + 10e-3) / 2,
            "draft": (30e-3 + 30e-3) / 2,
            "fusion": (20e-3 + 15e-3 + 10e-3 + 10e-3) / 2, "paged": 5e-3 / 2}
    got = {"window_s": s.window_s, "busy_s": s.busy_s,
           "draft": s.module_seconds("draft_step"),
           "fusion": s.ops["fusion"], "paged": s.op_seconds("paged_att")}
    # chip 0 idle: 40-50 (middle 45 in spin_round inside cell.step),
    # 60-80 (middle 70 in add_streams), 85-110 (outside every span);
    # chip 1 idle: 20-110 (middle 65, outside)
    gaps_want = {"engine.spin_round": 10e-3 / 2,
                 "engine.add_streams": 20e-3 / 2,
                 devtrace.OUTSIDE: (25e-3 + 90e-3) / 2}
    ok = all(abs(got[k] - want[k]) < 1e-12 for k in want)
    ok &= all(abs(s.gaps.get(k, 0) - v) < 1e-12 for k, v in gaps_want.items())
    ok &= abs(sum(s.gaps.values()) - (s.window_s - s.busy_s)) < 1e-12
    print(f"synthetic trace: {'ok' if ok else 'FAIL'} {got} gaps {s.gaps}")
    return ok


def recorded():
    with gzip.open(SAMPLE, "rt") as f:
        sample = json.load(f)
    for p in sample["planes"]:          # times are whole nanoseconds
        for ln in p["lines"]:
            ln["events"] = [[n, int(a), int(b)] for n, a, b in ln["events"]]
    s = devtrace.reduce_profile(devtrace.from_json(sample))
    grid = 100
    window = spans = None
    chips = []
    for p in sample["planes"]:
        for ln in p["lines"]:
            for n, a, b in ln["events"]:
                if n == devtrace.WINDOW:
                    window = (a, b)
        if p["name"].startswith("/device:TPU:"):
            chips.append({ln["name"]: ln["events"] for ln in p["lines"]})
    spans = [(a, b, n) for p in sample["planes"] if p["name"].startswith("/host:")
             for ln in p["lines"] for n, a, b in ln["events"]
             if n in devtrace.HOST_SPANS]
    lo, hi = window
    nbin = (hi - lo) // grid
    busy = 0.0
    idle_by = {}
    for c in chips:
        cover = np.zeros(nbin, bool)
        for _, a, b in c.get("XLA Ops", []):
            i, j = max(0, (a - lo) // grid), min(nbin, -(-(b - lo) // grid))
            if j > i:
                cover[i:j] = True
        busy += cover.sum() * grid * 1e-9 / len(chips)
        # idle bins named by the innermost span open at the bin
        name = np.full(nbin, devtrace.OUTSIDE, object)
        width = np.full(nbin, np.inf)
        for a, b, n in spans:
            i, j = max(0, (a - lo) // grid), min(nbin, (b - lo) // grid)
            if j > i:
                sel = slice(i, j)
                better = width[sel] > (b - a)
                name[sel] = np.where(better, n, name[sel])
                width[sel] = np.where(better, b - a, width[sel])
        for n in set(name[~cover]):
            idle_by[n] = idle_by.get(n, 0.0) + float(
                np.sum(name[~cover] == n)) * grid * 1e-9 / len(chips)
    n_ops = sum(len(c.get("XLA Ops", [])) for c in chips)
    tol = 2 * grid * 1e-9 * (n_ops + len(spans) + 2)
    ok = abs(busy - s.busy_s) <= tol
    # per-activity idle time: a gap is named at its middle, the grid per
    # bin, so they agree up to the gaps that straddle a span's edge
    edge = sum(b - a for a, b in devtrace._complement(
        [(a, b) for c in chips for _, a, b in c.get("XLA Ops", [])], lo, hi)
        if any(a < x < b for x in [e for sp in spans for e in sp[:2]])) * 1e-9
    for n in set(idle_by) | set(s.gaps):
        ok &= abs(idle_by.get(n, 0.0) - s.gaps.get(n, 0.0)) <= tol + edge
    total_mod = sum(min(b, hi) - max(a, lo) for c in chips
                    for _, a, b in c.get("XLA Modules", [])
                    if b > lo and a < hi) * 1e-9 / max(1, len(chips))
    ok &= abs(total_mod - sum(s.modules.values())) <= 1e-9
    print(f"recorded sample: {'ok' if ok else 'FAIL'} window {s.window_s:.6f} "
          f"s, busy {s.busy_s:.6f} s (grid {busy:.6f}), idle by activity "
          f"{ {k: round(v, 6) for k, v in s.gaps.items()} } (grid "
          f"{ {k: round(v, 6) for k, v in idle_by.items()} }), modules "
          f"{sorted(s.modules, key=s.modules.get, reverse=True)[:6]}")
    return ok


def main():
    ok = synthetic()
    if SAMPLE.exists():
        ok &= recorded()
    else:
        print(f"no recorded sample at {SAMPLE}")
        ok = False
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
