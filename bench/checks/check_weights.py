"""CPU check of the acceptance construction at smoke size.

    JAX_PLATFORMS=cpu python3 bench/checks/check_weights.py

Builds a smoke-size Qwen2 target and draft with ``archs/qwen2.py`` (the
construction of the configuration file, at small widths), runs the float32
reference over sequences that stay in one cluster (as served text does),
and measures at every position the acceptance per drafted token, the
overlap sum_y min(p(y), q(y)) of the target's distribution and the draft's
top-vhat renormalized one.  Each task's mean must lie within TOL of its
stated rate, and the mass outside the current cluster must be negligible.
"""

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import weights  # noqa: E402
from archs import qwen2 as qwen2_arch  # noqa: E402
from references import qwen2  # noqa: E402

TOL = 0.05          # per-task mean acceptance against its stated rate
N_SEQ, T = 12, 48   # sequences per task, tokens per sequence


def main():
    config = json.loads((BENCH / "configs" / "qwen2.5-3b.d0.5b.json")
                        .read_text())
    acc = dict(config["acceptance"], code_dims=32)
    vhat = config["serving"]["vhat"]
    tgt = qwen2_arch.smoke(4, 256, 4, 512, vocab=2048)
    drf = qwen2_arch.smoke(2, 128, 4, 256, vocab=2048)
    V, m = tgt["vocab_size"], acc["cluster_size"]
    kt, kd = weights.pair_keys(20261017)
    pt = qwen2_arch.make_params(tgt, acc, kt, "float32")
    pd = qwen2_arch.make_params(drf, acc, kd, "float32")
    n_tasks = len(acc["task_sigma"])
    per = (V // m) // n_tasks * m
    rng = np.random.default_rng(0)
    ok = True
    for task in range(n_tasks):
        toks = np.zeros((N_SEQ, T), np.int32)
        for i in range(N_SEQ):
            first = rng.integers(task * per, (task + 1) * per)
            c = first // m
            toks[i, 0] = first
            toks[i, 1:] = rng.integers(c * m, (c + 1) * m, T - 1)
        ht = qwen2.hidden_states(pt, tgt, toks)
        hd = qwen2.hidden_states(pd, drf, toks)
        overlap, outside = [], []
        for i in range(N_SEQ):
            p = jnp.exp(qwen2.sequence_log_probs(pt, ht[i]))
            q = jnp.exp(qwen2.sequence_log_probs(pd, hd[i]))
            vals, idx = jax.lax.top_k(q, vhat)
            qt = jnp.zeros_like(q).at[jnp.arange(T)[:, None], idx].set(
                vals / vals.sum(-1, keepdims=True))
            overlap.append(np.asarray(jnp.minimum(p, qt).sum(-1))[1:])
            c = toks[i, 0] // m
            inside = np.zeros(V, bool)
            inside[c * m:(c + 1) * m] = True
            outside.append(float(np.asarray(p)[1:, ~inside].sum(-1).max()))
        got = float(np.mean(overlap))
        want = acc["task_alpha"][task]
        good = abs(got - want) <= TOL and max(outside) < 1e-3
        ok &= good
        print(f"task {acc['tasks'][task]}: acceptance {got:.4f} (stated "
              f"{want}), target mass outside the cluster at most "
              f"{max(outside):.2e} -> {'ok' if good else 'FAIL'}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
