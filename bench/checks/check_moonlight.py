"""The Moonlight configuration at smoke size on the CPU: the check passes the
program and fails a planted fault.

    JAX_PLATFORMS=cpu python3 bench/checks/check_moonlight.py [--trace 1]

Drives whole runs through ``rehearse_cpu``'s path (the harness's look for a
chip skipped, interpret kernels) with the configuration's target section
replaced by a smoke-size DeepSeek-V3 section of the same shape
(``archs.deepseek_v3.smoke``: MLA with a rope part, a leading dense layer,
4 experts held of a 16-wide router, shared experts, untied head) and its
reference, ``references.moonlight_qwen2``.  The pair is served in float32,
so the check takes the float32 smoke cell's limits, not the chip's:

  clean          ``correct`` must come out true;
  softmax_route  the program routes by softmax over the router logits
                 (the reference keeps the sigmoid with its bias), so the
                 target's experts and gates differ: ``correct`` must come
                 out false.

With ``--trace 1`` the clean run also reads the per-layer metrics that the
CPU can give (the program's counters, not the device's).
"""

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_KERNELS", "interpret")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "checks"))

import jax  # noqa: E402

import harness  # noqa: E402
from archs import deepseek_v3  # noqa: E402
from rehearse_cpu import smoke_cell  # noqa: E402

NAME = "moonlight.chat"


def moonlight_cell():
    """(spec, workload, config, traffic) of a smoke-size copy of the cell."""
    spec, wl, config, traffic = smoke_cell(NAME)
    base = json.loads((BENCH / "configs" / "moonlight-16b-a3b.ep4.d0.5b.json")
                      .read_text())
    config["reference"] = base["reference"]
    config["target"] = deepseek_v3.smoke()
    wl["config"] = config["name"] = base["name"]
    return spec, wl, config, traffic


def softmax_route(served):
    """Plant the fault: the program's router takes softmax over its logits
    in place of the bias-corrected sigmoid (traced from here on)."""
    from repro.models import layers

    def route(params, x, top_k, routed_scale):
        logits = jax.numpy.matmul(x.astype(jax.numpy.float32),
                                  params["router"])
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        gates = gates / jax.numpy.sum(gates, axis=-1, keepdims=True)
        return idx, gates * routed_scale

    layers.route_sigmoid = route


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seed", default="20261021")
    a = ap.parse_args()
    from repro.models import layers

    route = layers.route_sigmoid
    results = {}
    for fault in (None, softmax_route):
        try:
            results[fault.__name__ if fault else "clean"] = harness.run(
                ["--workload", NAME, "--seed", a.seed, "--seconds", "4",
                 "--trace", a.trace if fault is None else "0"],
                allow_cpu=True, cell=moonlight_cell(),
                hooks={"served": fault} if fault else None)
        finally:
            layers.route_sigmoid = route
            jax.clear_caches()
    for name, r in results.items():
        print(f"{name}: correct {r['correct']}", flush=True)
    if not results["clean"]["correct"] or results["softmax_route"]["correct"]:
        print("FAIL: the check must pass the program and fail the fault")
        sys.exit(1)
    print("ok: the program passed the check and the fault failed it")


if __name__ == "__main__":
    main()
