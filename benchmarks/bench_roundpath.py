"""Compiled round path: eager vs jitted vs jitted+donated steady-state cost.

The tentpole claim this bench guards: compiling the three row-subset round
steps (``draft_rows`` / ``verify_rows`` / ``commit_rows``) into jitted step
functions — with the KV pools and stream-state buffers DONATED and the
committed-token emission as the round's single device->host fetch — makes a
round materially faster than op-by-op eager dispatch *without changing a
single committed token*.

Rows:

* **roundpath/eager|jit|jit_donate** — steady-state ``us_per_round`` over
  the same seeded round schedule (identical keys, prompts, params), plus
  the one-time warmup compile seconds for the jitted modes.  Timing is
  host-gated in the regression diff.
* **roundpath/compare** — the structural gate: ``bit_identical`` committed
  tokens across all three modes, ``n_host_syncs == 1`` per round,
  ``retraces == 0`` after ``warmup(buckets)``, ``step_shapes`` bounded at
  3 (draft/verify/commit at the single bucket), and the headline
  ``speedup_donate >= 1.3`` against eager.
* **roundpath/tree_build** — ``build_token_tree`` with the engine's pooled
  ``TreeScratch`` vs fresh per-call allocation (the ``engine.tree_build``
  span's host-side cost).

``--smoke`` writes ``BENCH_roundpath.json`` and exits nonzero when the
structural gate fails.

Usage::

    PYTHONPATH=src python -m benchmarks.bench_roundpath           # print rows
    PYTHONPATH=src python -m benchmarks.bench_roundpath --smoke   # CI gate
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_roundpath.json")

B, L, VHAT, MAX_LEN = 4, 4, 64, 96
SPEEDUP_GATE = 1.3


def _build(mode: str, seed: int):
    import jax

    from repro.configs import get_config
    from repro.serving.spec_engine import SpecEngine

    tcfg = get_config("qwen2.5-3b").smoke()
    dcfg = tcfg.replace(num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
                        head_dim=16, d_ff=64, name="draft-smoke")
    eng = SpecEngine(tcfg, dcfg, max_len=MAX_LEN, cache_kind="paged",
                     num_pages=B * 2 * (MAX_LEN // 16), compile_mode=mode)
    eng.init_params(jax.random.PRNGKey(seed))
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, 10), 0,
                                 tcfg.vocab_size)
    return eng, eng.start(prompts)


def run_mode(mode: str, seed: int, warm_rounds: int, rounds: int) -> dict:
    """One engine, one seeded round schedule; every mode replays the same
    keys so committed tokens are comparable bit-for-bit."""
    import jax

    eng, st = _build(mode, seed)
    compile_s = 0.0
    if mode != "eager":
        st, info = eng.warmup(st, [(B, L)], vhat=VHAT)
        compile_s = float(sum(info.values()))
    base = jax.random.PRNGKey(seed + 1000)
    lengths = np.full(B, L)
    for r in range(warm_rounds):
        st, _, _ = eng.spin_round(st, lengths, jax.random.fold_in(base, r),
                                  vhat=VHAT)
    retraced: list = []
    eng.on_step_trace = retraced.append
    h0 = eng.host_syncs
    t0 = time.perf_counter()
    for r in range(warm_rounds, warm_rounds + rounds):
        # each round ends in the engine's single device->host emission
        # fetch, so wall time per iteration is device-synchronized
        st, _, _ = eng.spin_round(st, lengths, jax.random.fold_in(base, r),
                                  vhat=VHAT)
    wall = time.perf_counter() - t0
    return {
        "mode": mode,
        "us_per_round": wall / rounds * 1e6,
        "compile_s": compile_s,
        "host_syncs_per_round": (eng.host_syncs - h0) / rounds,
        "retraces": len(retraced),
        # the round steps' traced shapes; start()'s prefill is not a round
        "step_shapes": sum(k[0] != "prefill" for k in eng.step_shapes),
        "committed": [list(map(int, c)) for c in st.committed],
    }


def run_roundpath(seed: int, warm_rounds: int, rounds: int) -> list[dict]:
    res = {m: run_mode(m, seed, warm_rounds, rounds)
           for m in ("eager", "jit", "jit+donate")}
    rows = []
    for m, slug in (("eager", "eager"), ("jit", "jit"),
                    ("jit+donate", "jit_donate")):
        r = res[m]
        rows.append({
            "name": f"roundpath/{slug}",
            "derived": (f"us_per_round={r['us_per_round']:.0f} "
                        f"compile_s={r['compile_s']:.1f} "
                        f"host_syncs/round={r['host_syncs_per_round']:.1f}"),
            "us_per_round": r["us_per_round"],
            "compile_s": r["compile_s"],
            "rounds": rounds,
        })
    eager, jit, don = res["eager"], res["jit"], res["jit+donate"]
    bit_identical = (jit["committed"] == eager["committed"]
                     and don["committed"] == eager["committed"])
    speedup_jit = eager["us_per_round"] / jit["us_per_round"]
    speedup_donate = eager["us_per_round"] / don["us_per_round"]
    ok = (bit_identical and speedup_donate >= SPEEDUP_GATE
          and don["host_syncs_per_round"] == 1.0 and don["retraces"] == 0)
    rows.append({
        "name": "roundpath/compare",
        "derived": (f"speedup_jit={speedup_jit:.2f}x "
                    f"speedup_donate={speedup_donate:.2f}x "
                    f"bit_identical={bit_identical} "
                    f"n_host_syncs={don['host_syncs_per_round']:.0f} "
                    f"retraces={don['retraces']} ok={ok}"),
        "speedup_jit": speedup_jit,
        "speedup_donate": speedup_donate,
        "bit_identical": int(bit_identical),
        "n_host_syncs": don["host_syncs_per_round"],
        "retraces": don["retraces"],
        "step_shapes": don["step_shapes"],
        "gate_ok": int(ok),
    })
    return rows


def run_tree_build(seed: int, iters: int = 200, Bt: int = 8, J: int = 4,
                   Lt: int = 8, Vhat: int = 512) -> dict:
    """Host-side trie packing: pooled TreeScratch vs fresh allocations.

    Benched at a serving-scale shape — the pool's high-water reset touches
    only the node prefix the last round wrote, while fresh allocation
    zero-fills the full (B, J*L, Vhat) q-summary buffers every call."""
    from repro.core.token_tree import TreeScratch, build_token_tree

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 1000, (Bt, J, Lt)).astype(np.int32)
    # duplicate draft 0 into draft 1's prefix so the trie actually dedups
    tokens[:, 1, : Lt // 2] = tokens[:, 0, : Lt // 2]
    probs = rng.random((Bt, J, Lt)).astype(np.float32)
    q_idx = rng.integers(0, 1000, (Bt, J, Lt, Vhat)).astype(np.int32)
    q_val = rng.random((Bt, J, Lt, Vhat)).astype(np.float32)
    lengths = np.full(Bt, Lt, np.int64)

    def loop(scratch):
        t0 = time.perf_counter()
        for _ in range(iters):
            build_token_tree(tokens, probs, q_idx, q_val, lengths,
                             scratch=scratch)
        return (time.perf_counter() - t0) / iters * 1e6

    loop(None)  # warm numpy dispatch paths
    fresh_us = loop(None)
    scratch = TreeScratch()
    scratch_us = loop(scratch)
    ratio = fresh_us / scratch_us if scratch_us else 0.0
    return {
        "name": "roundpath/tree_build",
        "derived": (f"us_per_call={scratch_us:.0f} (scratch) "
                    f"fresh={fresh_us:.0f}us ratio={ratio:.2f}x "
                    f"B={Bt} J={J} L={Lt} Vhat={Vhat}"),
        "us_per_call": scratch_us,
        "fresh_us_per_call": fresh_us,
        "iters": iters,
    }


def run(smoke: bool = False, seed: int = 0, warm_rounds: int = 2,
        rounds: int = 8, out_path: str | None = None) -> list[dict]:
    rows = run_roundpath(seed, warm_rounds, rounds)
    rows.append(run_tree_build(seed))
    if smoke:
        gate_ok = bool(rows[-2]["gate_ok"])
        if not gate_ok:
            raise SystemExit("roundpath smoke FAILED: "
                             + rows[-2]["derived"])
        from .common import write_rows_json
        write_rows_json(out_path or BENCH_PATH, rows)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=8,
                    help="measured steady-state rounds per mode")
    ap.add_argument("--warm-rounds", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: bit-identity, 1 host sync/round, zero "
                         "retraces after warmup, >=1.3x donated speedup; "
                         "writes BENCH_roundpath.json")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="dump rows as JSON (CI artifact)")
    ap.add_argument("--out", type=str, default=None, metavar="PATH",
                    help="where --smoke writes its rows (default: the "
                         "committed repo-root BENCH_roundpath.json)")
    args = ap.parse_args()
    rows = run(smoke=args.smoke, seed=args.seed,
               warm_rounds=args.warm_rounds, rounds=args.rounds,
               out_path=args.out)
    for r in rows:
        print(r["name"], r["derived"])
    if args.json:
        from .common import write_rows_json
        write_rows_json(args.json, rows)


if __name__ == "__main__":
    main()
