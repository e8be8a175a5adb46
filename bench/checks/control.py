"""Readings of the program and of its control, for setting the limits.

    python3 bench/checks/control.py <cell> <seconds> <seed> [<seed> ...]
    JAX_PLATFORMS=cpu python3 bench/checks/control.py --smoke [<seed> ...]

For each seed, one run of the cell in this one process (the benchmark's
own set-up, window and check, a short window at the cell's load), whose
check replays the served decisions twice: with the program's decisions
(the readings of a sound run) and with the control's, the float32
reference with every matrix product rounded to float8_e4m3 (the control:
the precision below the configuration's bfloat16).  The control's readings
go through the harness's own comparison (``harness.judge``) against the
configuration's limits, as the program's do.  Prints one JSON line per
seed, with ``correct`` (the program's run) and ``control_correct`` and, at
the end, each number's largest program reading and smallest control
reading.  Exits non-zero where a seed's program run is not correct or its
control is.  ``--smoke`` does the same at smoke size on the CPU with
interpret kernels.
"""

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "checks"))


def main(argv):
    smoke = argv[:1] == ["--smoke"]
    if smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("REPRO_KERNELS", "interpret")
        cell_name, seconds, seeds = "smoke", "3", argv[1:] or ["101", "202"]
    else:
        cell_name, seconds, seeds = argv[0], argv[1], argv[2:]
    import compare
    import harness

    cell = None
    if smoke:
        from rehearse_cpu import smoke_cell
        cell = smoke_cell("smoke")
    limits = (cell[2] if cell else harness.load_cell(
        harness.load_spec(), cell_name)[1])["limits"]
    rows = []
    for seed in seeds:
        got = {}
        result = harness.run(
            ["--workload", cell_name, "--seed", seed, "--seconds", seconds,
             "--trace", "0"], allow_cpu=smoke, cell=cell,
            hooks={"precisions": ("program", "fp8"),
                   "readings": got.update})
        control = got.get("fp8")
        row = {"seed": seed, "correct": result["correct"],
               "control_correct": (control is not None
                                   and harness.judge(control, limits)),
               "program": got.get("program"), "control": control,
               "counts": got.get("counts")}
        rows.append(row)
        print("control-row " + json.dumps(row), flush=True)
    summary = {}
    for k in compare.NUMBERS[:-1]:
        prog = [r["program"][k] for r in rows if r["program"]]
        ctrl = [r["control"][k] for r in rows if r["control"]]
        summary[k] = {"program_max": max(prog, default=None),
                      "control_min": min(ctrl, default=None),
                      "control_max": max(ctrl, default=None)}
    print("control-summary " + json.dumps(summary), flush=True)
    bad = [r["seed"] for r in rows if not r["correct"] or r["control_correct"]]
    if bad:
        print(f"FAIL: seeds {bad}: the program's run was not correct or the "
              "control's was", flush=True)
        return 1
    print(f"ok: {len(rows)} seeds, every program run correct and every "
          "control not", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

