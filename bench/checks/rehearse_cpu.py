"""CPU rehearsal of one benchmark run at smoke size.

    JAX_PLATFORMS=cpu python3 bench/checks/rehearse_cpu.py [--seconds 3] [--tree] [--trace 1]

Drives the whole harness (weights and acceptance construction, warm-up,
gateway, load generator, window, metric readers, the check against the
reference) on a tiny pair with the Pallas kernels in interpret mode.  It
proves the control flow only: every time it prints is a CPU time, and the
device readings are absent because the CPU has no device trace.  The
measuring command (``bench/run.py``) still refuses to run without a TPU.
"""

import argparse
import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_KERNELS", "interpret")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from archs import qwen2  # noqa: E402


def smoke_cell(name: str, tree: bool = False, mix: str = "chat"):
    """(spec, workload, config, traffic) of a smoke-size copy of a cell."""
    spec = harness.load_spec()
    base = json.loads((BENCH / "configs" / ("qwen2.5-1.5b.d0.5b.tree.json"
                                            if tree else
                                            "qwen2.5-3b.d0.5b.json"))
                      .read_text())
    config = copy.deepcopy(base)
    config["name"] = name
    config["target"] = qwen2.smoke(2, 128, 4, 256)
    config["draft"] = qwen2.smoke(1, 64, 4, 128)
    config["serving"].update(max_batch=4, max_len=128, L_max=4,
                             dtype="float32")
    config["acceptance"].update(code_dims=16)
    # the smoke pair is served in float32, not bfloat16: its draft readings
    # lie near 6e-5 and the float8 control's near 0.08 (bench/checks/
    # control.py --smoke), so the draft's limit sits between those two
    config["limits"] = dict(config["limits"], draft_logq_err=0.01)
    traffic = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    traffic.update(prompt_len={"dist": "loguniform", "lo": 16, "hi": 40},
                   output_len={"dist": "loguniform", "lo": 16, "hi": 40},
                   clients=4, table_size=256, check_requests=3,
                   check_len=128)
    wl = {"name": name, "config": name, "traffic": mix, "chips": 1}
    return spec, wl, config, traffic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", default="3")
    ap.add_argument("--seed", default="12345678901")
    ap.add_argument("--tree", action="store_true")
    ap.add_argument("--trace", default="0")
    a = ap.parse_args()
    # named after the cell it copies, so that the metrics the benchmark
    # reads in that cell alone are read here too
    name = "q15b-tree.chat" if a.tree else "q3b.chat"
    cell = smoke_cell(name, tree=a.tree)
    harness.run(["--workload", name, "--seed", a.seed, "--seconds",
                 a.seconds, "--trace", a.trace], allow_cpu=True, cell=cell)


if __name__ == "__main__":
    main()
