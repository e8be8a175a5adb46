"""Record a small trace sample of one cell on the chip.

    python3 bench/checks/trace_sample.py <cell> <seed> <out.json.gz>

Runs the cell for 8 s with the profiler on (a normal ``--trace 1`` run),
keeps the plane, line and event structure of the busiest 150 ms of the
traced window as plain JSON (``devtrace.to_json``), and prints what the
trace holds: plane and line names, the busiest programs and operations.
The sample is what ``check_trace.py`` checks the reduction against.
"""

import bisect
import gzip
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402


def main(cell, seed, out):
    def keep(trace_dir):
        prof = devtrace.load(trace_dir)
        window = None
        for p in prof.planes:
            print("plane", p.name, [ln.name for ln in p.lines][:12])
            for ln in p.lines:
                for e in ln.events:
                    if e.name == devtrace.WINDOW:
                        window = (e.start_ns, e.end_ns)
        # the busiest 150 ms of the window, by device operations started
        starts = sorted(e.start_ns for p in prof.planes
                        if p.name.startswith("/device:TPU:")
                        for ln in p.lines if ln.name == "XLA Ops"
                        for e in ln.events)
        span = 150_000_000
        best, lo = -1, window[0]
        for i, t in enumerate(starts):
            if t + span > window[1]:
                break
            n = bisect.bisect_left(starts, t + span) - i
            if n > best:
                best, lo = n, t
        hi = lo + span
        sample = devtrace.to_json(prof, lo, hi)
        for p in sample["planes"]:
            for ln in p["lines"]:
                if ln["name"] == "" or p["name"].startswith("/host:"):
                    ln["events"] = [e for e in ln["events"]
                                    if e[0] in devtrace.HOST_SPANS
                                    or e[0] == devtrace.WINDOW]
        for p in sample["planes"]:
            for ln in p["lines"]:
                for e in ln["events"]:
                    if e[0] == devtrace.WINDOW:
                        e[1], e[2] = lo, hi
        with gzip.open(out, "wt") as f:
            json.dump(sample, f)
        s = devtrace.reduce_profile(prof)
        print("modules", sorted(s.modules.items(), key=lambda kv: -kv[1])[:25])
        print("ops", sorted(s.ops.items(), key=lambda kv: -kv[1])[:40])
        print("gaps", s.gaps, "busy", s.busy_s, "window", s.window_s)

    harness.run(["--workload", cell, "--seed", seed, "--seconds", "8",
                 "--trace", "1"], hooks={"trace": keep})


if __name__ == "__main__":
    main(*sys.argv[1:4])
