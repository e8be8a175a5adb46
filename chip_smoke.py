"""Bring-up smoke of the served path on one TPU chip.

    python chip_smoke.py

One process, no child processes.  It first runs each Pallas kernel of the
served round against its jnp oracle in ``kernels/ref.py`` at the widths of
the served pair.  Then it serves a full-width pair through the gateway:
target ``qwen2.5-3b``, draft ``qwen3.5-0.8b``, bf16 weights made from a
seed and a bf16 paged KV cache, on the compiled round path
(``jit+donate``).  The cell is built by ``repro.launch.gateway.build_cell``
and driven by ``run_loadgen`` through an in-process ``MultiSpinGateway``
(the path of ``gateway --smoke``), in two phases over the one set of
parameters:

  a. scheme ``hete``: J=1 rounds on the compiled draft/verify/commit steps;
  b. scheme ``multidraft`` with J=2: token-tree rounds through the paged
     tree-attention kernel and the scatter commit.

It exits 1 with the cause when JAX finds no TPU, when ``REPRO_KERNELS``
selects anything but the Pallas kernels, when a kernel misses its oracle,
when a kernel span ran another backend, when a request errors or times
out, when a committed token lies outside the vocabulary, or when a round
raises.  Tokens/s and TTFT printed here are readings of one short bring-up
run, not benchmark numbers.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TARGET, DRAFT = "qwen2.5-3b", "qwen3.5-0.8b"
MAX_BATCH, MAX_LEN, L_MAX = 8, 1024, 8    # pools: 1024 pages of 16 slots
HETE_REQUESTS, TREE_REQUESTS = 16, 4
REQUEST_TIMEOUT_S = 600.0                 # cold compiles included
SEED = 0

# bf16 inputs, float32 math inside the kernels; the oracle runs in float32
# at full matmul precision on the same bf16 values
ATTN_TOL = 2e-2          # atol and rtol of attention outputs (bf16 output)
PROB_RTOL = 1e-4         # gather_softmax_prob against the float32 oracle
CDF_TOL = 1e-4           # sampled token's CDF interval must hold u to this
TIE_GAP = 1e-3           # accept-test uniforms kept this far from the ratio


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind}); this smoke never falls back to the CPU")
    mode = os.environ.get("REPRO_KERNELS", "auto")
    if mode not in ("auto", "pallas"):
        fail(f"REPRO_KERNELS={mode!r}: the smoke runs the Pallas kernels "
             "(auto or pallas) only")
    return dev


# ---------------------------------------------------------------------------
# kernels against their oracles
# ---------------------------------------------------------------------------

def _tree_masks(rng, B: int, T: int):
    """(B, T, T) ancestor-or-self matrices of random trees rooted at node 0."""
    import numpy as np

    masks = np.zeros((B, T, T), bool)
    for b in range(B):
        for t in range(T):
            if t:
                masks[b, t] = masks[b, rng.integers(0, t)]
            masks[b, t, t] = True
    return masks


def _check_close(name: str, got, want, atol: float, rtol: float) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        fail(f"{name}: shape {got.shape} vs {want.shape}, or non-finite")
    err = float(np.max(np.abs(got - want)))
    if not np.allclose(got, want, atol=atol, rtol=rtol):
        fail(f"{name}: max |kernel - oracle| = {err:.3g} "
             f"(atol {atol}, rtol {rtol})")
    return err


def _check_attention(cfg, rng, key) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    B, ps, n_slots = 8, 16, 16
    P = B * n_slots + 32
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(key, 3)
    bf = lambda k, s: jax.random.normal(k, s, jnp.float32).astype(jnp.bfloat16)
    k_pool, v_pool = bf(kk, (P, ps, KV, D)), bf(kv, (P, ps, KV, D))
    f32 = lambda x: x.astype(jnp.float32)
    for T, tree in ((1, False), (9, False), (17, True)):
        q = bf(jax.random.fold_in(kq, T), (B, T, H, D))
        lengths = rng.integers(1, n_slots * ps - T, B)
        table = rng.permutation(P)[:B * n_slots].reshape(B, n_slots)
        # slots past the window are unmapped, as the engine leaves them
        table[np.arange(n_slots)[None, :] * ps >= (lengths + T)[:, None]] = -1
        table, lens = jnp.asarray(table, jnp.int32), jnp.asarray(lengths,
                                                                 jnp.int32)
        if tree:
            name = f"paged_tree_attention {cfg.name} T={T}"
            mask = jnp.asarray(_tree_masks(rng, B, T))
            got = ops.paged_tree_attention(q, k_pool, v_pool, table, lens,
                                           mask)
            with jax.default_matmul_precision("highest"):
                want = ref.paged_tree_attention_ref(
                    f32(q), f32(k_pool), f32(v_pool), table, lens, mask)
        else:
            name = f"paged_attention {cfg.name} T={T}"
            got = ops.paged_attention(q, k_pool, v_pool, table, lens)
            with jax.default_matmul_precision("highest"):
                want = ref.paged_attention_ref(f32(q), f32(k_pool),
                                               f32(v_pool), table, lens)
        err = _check_close(name, got, want, ATTN_TOL, ATTN_TOL)
        print(f"kernel {name}: max abs err {err:.3g} vs oracle (ok)")


def _residual_cdf(logits_row, q_idx, q_val):
    """Host float64 normalized CDF of max(softmax(logits) - q, 0)."""
    import numpy as np

    x = np.asarray(logits_row, np.float64)
    p = np.exp(x - x.max())
    p /= p.sum()
    q = np.zeros_like(p)
    np.add.at(q, np.asarray(q_idx), np.asarray(q_val, np.float64))
    r = np.maximum(p - q, 0.0)
    return np.cumsum(r) / r.sum()


def _token_in_cdf(cdf, tok: int, u: float) -> bool:
    lo = cdf[tok - 1] if tok > 0 else 0.0
    return lo - CDF_TOL <= u <= cdf[tok] + CDF_TOL


def _check_verify(cfg, rng, key) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    B, L, Vhat, V = 8, 8, 64, cfg.vocab_size
    ks = jax.random.split(key, 6)
    logits = (4.0 * jax.random.normal(ks[0], (B, L + 1, V))).astype(
        jnp.bfloat16)
    tokens = jax.random.categorical(ks[1], logits[:, :L].astype(jnp.float32))
    p_t = ref.gather_softmax_prob_ref(logits[:, :L].reshape(B * L, V),
                                      tokens.reshape(-1)).reshape(B, L)
    q0 = jnp.clip(p_t * jax.random.uniform(ks[2], (B, L), minval=0.5,
                                           maxval=1.5), 1e-6, 0.9)
    rest = jax.random.dirichlet(ks[3], jnp.ones(Vhat - 1), (B, L))
    q_val = jnp.concatenate([q0[..., None], (1 - q0)[..., None] * rest], -1)
    q_idx = jnp.concatenate(
        [tokens[..., None],
         jax.random.randint(ks[4], (B, L, Vhat - 1), 0, V)], -1)
    # accept-test uniforms kept clear of min(1, p/q): a near tie could flip
    # on float rounding alone and says nothing about the kernel
    thr = np.minimum(1.0, np.asarray(p_t / q0, np.float64))
    u_acc = rng.uniform(size=(B, L))
    near = np.abs(u_acc - thr) < TIE_GAP
    u_acc = np.where(near, np.clip(thr - 2 * TIE_GAP, 0.0, 1.0), u_acc)
    u_acc = jnp.asarray(u_acc, jnp.float32)
    u_res = jnp.asarray(rng.uniform(size=B), jnp.float32)
    dlen = jnp.asarray(rng.integers(1, L + 1, B), jnp.int32)

    got = ops.fused_verify_sample(logits, tokens, q0, q_idx, q_val, u_acc,
                                  u_res, dlen)
    want = ref.fused_verify_sample_ref(logits, tokens, q0, q_idx, q_val,
                                       u_acc, u_res, dlen)
    acc, n_acc, cal = (np.asarray(x) for x in got)
    if not np.array_equal(acc, np.asarray(want[0])):
        fail("fused_verify_sample: accept mask differs from the oracle")
    if not np.array_equal(n_acc, np.asarray(want[1])):
        fail(f"fused_verify_sample: n_acc {n_acc} vs oracle "
             f"{np.asarray(want[1])}")
    same = int(np.sum(cal == np.asarray(want[2])))
    lg, qi, qv = np.asarray(logits, np.float32), np.asarray(q_idx), \
        np.asarray(q_val)
    for b in range(B):
        sel = min(int(n_acc[b]), L - 1)
        cdf = _residual_cdf(lg[b, sel], qi[b, sel], qv[b, sel])
        if not _token_in_cdf(cdf, int(cal[b]), float(u_res[b])):
            fail(f"fused_verify_sample: row {b} token {cal[b]} is not the "
                 f"inverse-CDF pick of u={float(u_res[b]):.6f}")
    print(f"kernel fused_verify_sample V={V} L={L}: accept and n_acc equal "
          f"to the oracle (mean n_acc {n_acc.mean():.2f}); {same}/{B} "
          f"calibrated tokens identical, all {B} inverse-CDF picks within "
          f"{CDF_TOL} (ok)")

    # the tree path's separate gather and residual kernels
    flat = logits[:, :L].reshape(B * L, V)
    p_got = ops.gather_softmax_prob(flat, tokens.reshape(-1))
    err = _check_close("gather_softmax_prob", p_got, p_t.reshape(-1), 0.0,
                       PROB_RTOL)
    print(f"kernel gather_softmax_prob V={V} N={B * L}: max abs err "
          f"{err:.3g} vs oracle (ok)")
    p = jax.nn.softmax(logits[:, 0].astype(jnp.float32), axis=-1)
    q = jax.nn.softmax(logits[:, 1].astype(jnp.float32), axis=-1)
    tok = np.asarray(ops.residual_sample(p, q, u_res))
    same = int(np.sum(tok == np.asarray(ref.residual_sample_ref(p, q,
                                                                u_res))))
    pn, qn = np.asarray(p, np.float64), np.asarray(q, np.float64)
    for b in range(B):
        r = np.maximum(pn[b] - qn[b], 0.0)
        cdf = np.cumsum(r) / r.sum()
        if not _token_in_cdf(cdf, int(tok[b]), float(u_res[b])):
            fail(f"residual_sample: row {b} token {tok[b]} is not the "
                 f"inverse-CDF pick of u={float(u_res[b]):.6f}")
    print(f"kernel residual_sample V={V}: {same}/{B} tokens identical to the "
          f"oracle, all {B} inverse-CDF picks within {CDF_TOL} (ok)")


def check_kernels(target: str, draft: str, seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    for i, name in enumerate((target, draft)):
        _check_attention(get_config(name), rng, jax.random.fold_in(key, i))
    _check_verify(get_config(target), rng, jax.random.fold_in(key, 2))


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

class _TokenLog:
    """Cell listener: every request's generated token ids, read right after
    each round (inside ``cell.step``, before the gateway drops finished
    requests' tokens)."""

    def __init__(self, backend):
        self.backend = backend
        self.tokens: dict[int, list[int]] = {}

    def on_round(self, rec):
        for rid in rec.rids.tolist():
            self.tokens[int(rid)] = self.backend.stream_tokens(int(rid))


async def _serve_phase(cell, n_requests: int, seed: int):
    from repro.serving.gateway import (
        GatewayConfig,
        LoadGenConfig,
        MultiSpinGateway,
        run_loadgen,
    )

    gw = MultiSpinGateway(cell, GatewayConfig(port=0, idle_wait_s=0.02))
    await gw.start()
    try:
        report = await run_loadgen("127.0.0.1", gw.port, LoadGenConfig(
            rate_per_s=32.0, n_requests=n_requests,
            max_new_tokens_choices=(16, 32), timeout_s=REQUEST_TIMEOUT_S,
            seed=seed))
    finally:
        await gw.stop()
    if gw.round_error is not None:
        fail(f"a round raised: {type(gw.round_error).__name__}: "
             f"{gw.round_error}")
    return report


def run_phase(label: str, cell, n_requests: int, seed: int, vocab: int):
    import numpy as np

    log = cell.add_listener(_TokenLog(cell.backend))
    r0 = len(cell.history)
    report = asyncio.run(_serve_phase(cell, n_requests, seed))
    cell.remove_listener(log)
    rounds = cell.history[r0:]
    if report["n_error"] or report["n_ok"] != n_requests:
        fail(f"phase {label}: {report['n_error']} of {n_requests} requests "
             f"failed: {report['errors']}")
    toks = [t for ts in log.tokens.values() for t in ts]
    if len(log.tokens) != n_requests or not toks:
        fail(f"phase {label}: tokens seen for {len(log.tokens)} of "
             f"{n_requests} requests")
    bad = [t for t in toks if not 0 <= t < vocab]
    if bad:
        fail(f"phase {label}: {len(bad)} committed tokens outside "
             f"[0, {vocab}): {bad[:8]}")
    acc = [float(np.mean(np.asarray(r.accepted)[np.asarray(r.active, bool)]))
           for r in rounds if np.any(r.active)]
    widths = sorted({int(r.draft_width) for r in rounds})
    print(f"phase {label}: {n_requests} requests ok, {report['tokens']} "
          f"tokens streamed ({len(toks)} committed, all in [0, {vocab})), "
          f"{len(rounds)} rounds, J per round {widths}, mean accepted "
          f"length {np.mean(acc):.3f} (incl. bonus)")
    print(f"phase {label} bring-up readings (one short run, not a "
          f"benchmark): wall {report['wall_s']:.1f}s, committed tokens/s "
          f"{report['tokens_per_s']:.1f}, p50 TTFT "
          f"{report['ttft_s']['p50']:.3f}s")
    return widths


def main() -> None:
    dev = require_tpu()
    import jax

    from repro.configs import get_config
    from repro.core.schemes import parse_scheme_args
    from repro.launch.gateway import build_cell, build_parser
    from repro.obs import trace
    from repro.serving.cell import MultiSpinCell
    from repro.serving.compiled import setup_compilation_cache

    cache_dir = setup_compilation_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    check_kernels(TARGET, DRAFT, SEED)
    print(f"kernel parity: {time.perf_counter() - t0:.1f}s")

    # the tracer is installed before the cell is built: jitted round steps
    # dispatch their kernels at trace time (warmup), eager tree rounds on
    # every call, and both must show the Pallas backend
    tracer = trace.install(trace.Tracer(capacity=1 << 20))
    gw_args = build_parser().parse_args([
        "--backend", "engine", "--arch", TARGET, "--draft-arch", DRAFT,
        "--max-len", str(MAX_LEN), "--max-batch", str(MAX_BATCH),
        "--compile", "jit+donate",
        "--warmup-lengths", ",".join(str(L) for L in range(1, L_MAX + 1)),
        "--L-max", str(L_MAX), "--scheme", "hete", "--seed", str(SEED)])
    t0 = time.perf_counter()
    cell = build_cell(gw_args)
    print(f"build_cell (bf16 init of both models, prefill, warmup compile "
          f"of {L_MAX} round buckets): {time.perf_counter() - t0:.1f}s")
    vocab = get_config(TARGET).vocab_size

    run_phase("a (hete, J=1, compiled)", cell, HETE_REQUESTS, SEED, vocab)
    tree_cfg = dataclasses.replace(
        cell.config, scheme="multidraft",
        scheme_params=parse_scheme_args("multidraft", ["J_min=2", "J_max=2"]))
    tree_cell = MultiSpinCell(tree_cfg, backend=cell.backend,
                              rng=cell.rng)
    widths = run_phase("b (multidraft, J=2, tree)", tree_cell, TREE_REQUESTS,
                       SEED + 1, vocab)
    if min(widths) < 2:
        fail(f"phase b ran rounds with J={widths}; tree rounds need J >= 2")

    trace.uninstall()
    kernels: dict[str, set] = {}
    for sp in tracer.snapshot():
        if sp.cat == "kernel":
            kernels.setdefault(sp.name, set()).add((sp.args or {}).get(
                "backend"))
    off = {n: b for n, b in kernels.items() if b != {"pallas"}}
    if off:
        fail(f"kernel spans not on the pallas backend: {off}")
    need = {"ops.paged_attention", "ops.paged_tree_attention",
            "ops.fused_verify_sample", "ops.gather_softmax_prob",
            "ops.residual_sample"}
    if need - set(kernels):
        fail(f"the served path dispatched no {sorted(need - set(kernels))}")
    print(f"kernel spans: {sorted(kernels)} all on backend pallas")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
          f"bytes_limit {stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
