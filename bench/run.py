"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, in
this one process: set-up (weights from the seed, warm-up from the
compilation cache at ``<checkout>/.jax_cache``), the measured window
through an in-process gateway, then the check against the plain reference.
Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""

import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
