"""The check must fail a run whose timed path is broken.

    JAX_PLATFORMS=cpu python3 bench/checks/check_faults.py [--tree]

Drives whole runs at smoke size on the CPU (the harness's look for a chip
skipped, interpret kernels), each with one fault planted underneath the
timed path, and each run's ``correct`` must come out false:

  alter_tokens   a token altered where it is produced: the target-pass
                 step of the compiled round (``verify`` for J=1,
                 ``tree_verify`` for token trees) returns, in every round,
                 each row's token at its first rejection (or its bonus
                 token) moved to the other half of the vocabulary;
  drop_qkv_bias  the program serves both models without their QKV biases
                 (a model that ignores a term of the configuration).

A cell on one chip has no exchange between chips, and a served model has no
optimizer state or batch mean, so the other faults of the list do not
apply.
"""

import argparse
import dataclasses
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
from rehearse_cpu import smoke_cell  # noqa: E402

def alter_tokens(served):
    """Plant the fault: the target pass's produced token is replaced."""
    steps = served.engine._steps
    name = "verify" if served.J == 1 else "tree_verify"
    step = getattr(steps, name)
    V = served.tcfg.vocab_size

    def faulty(*args, **kw):
        res, kv = step(*args, **kw)
        tok = res.output_tokens
        at = jax.numpy.arange(tok.shape[1])[None, :] == res.accept_counts[:, None]
        return dataclasses.replace(
            res, output_tokens=jax.numpy.where(at, (tok + V // 2) % V, tok)), kv

    setattr(steps, name, faulty)


def drop_qkv_bias(served):
    """Plant the fault: the served weights lose their QKV biases."""
    eng = served.engine
    for which in ("t_params", "d_params"):
        params = getattr(eng, which)
        attn = dict(params["blocks"]["attn"])
        for b in ("bq", "bk", "bv"):
            attn[b] = jax.numpy.zeros_like(attn[b])
        blocks = dict(params["blocks"], attn=attn)
        setattr(eng, which, dict(params, blocks=blocks))


FAULTS = {"alter_tokens": alter_tokens, "drop_qkv_bias": drop_qkv_bias}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", action="store_true")
    ap.add_argument("--fault", choices=sorted(FAULTS), action="append")
    a = ap.parse_args()
    passed = []
    for name in a.fault or sorted(FAULTS):
        result = harness.run(["--workload", "smoke", "--seed", "31337",
                              "--seconds", "6", "--trace", "0"],
                             allow_cpu=True,
                             cell=smoke_cell("smoke", tree=a.tree),
                             hooks={"served": FAULTS[name]})
        print(f"fault {name}: correct {result['correct']}", flush=True)
        if result["correct"]:
            passed.append(name)
    if passed:
        print(f"FAIL: the check passed the faults {passed}")
        sys.exit(1)
    print("ok: every fault failed the check")


if __name__ == "__main__":
    main()
