"""Share of its roofline that the grouped expert matmul (the dropless MoE's
SwiGLU over the (token, held expert) pairs, in every target pass: verify
and prefill) reaches in the traced window, in percent.

The work comes from the program's counters over the window:
``engine.moe_routed_pairs`` pairs computed and ``engine.moe_experts_touched``
held experts that got a token, summed over layers and target passes.  FLOPs
are pairs x 6 d F; bytes are touched experts x 3 d F x 2 B (each expert's
weights read once per layer and pass) plus each pair's input row (bf16) and
output row (float32).  Both are lower bounds of what the kernel does (it
computes whole row tiles).  The counters cover the whole window and the
trace its middle: the pairs are scaled by the share of the window's
dispatched target tokens (round windows and bucketed prefill rows) that
fall in the trace, the experts touched by its share of target passes."""

import arch
import roofline

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = r"expert_matmul"


def _bucket(M: int, max_len: int) -> int:
    """The engine's prompt bucket (``SpecEngine.prompt_bucket``)."""
    b = 8
    while b < M:
        b *= 2
    return max(M, min(b, max_len))


def _dispatched(run, t0=None, t1=None):
    """(target tokens, target passes) dispatched in [t0, t1)."""
    max_len = int(run.config["serving"]["max_len"])
    rounds = run.window_rounds(t0, t1)
    prefills = run.window_prefills(t0, t1)
    tokens = sum(len(r.lengths) * (int(max(r.lengths)) + 1) for r in rounds)
    tokens += sum(n * (_bucket(M, max_len) - 1) for _, _, n, M in prefills)
    return tokens, len(rounds) + len(prefills)


def _work(run):
    c = run.counters
    pairs = c.get("engine.moe_routed_pairs", 0)
    touched = c.get("engine.moe_experts_touched", 0)
    tok_w, pass_w = _dispatched(run)
    tok_t, pass_t = _dispatched(run, *run.trace_window)
    if not pairs or not tok_w or not pass_w:
        return None
    pairs *= tok_t / tok_w
    touched *= pass_t / pass_w
    m = run.config["target"]
    d, F = m["hidden_size"], m["moe_intermediate_size"]
    flops = 6.0 * d * F * pairs
    nbytes = touched * arch.of(m).expert_bytes(m) + pairs * d * (2 + 4)
    return flops, nbytes


def read(run):
    if run.trace is None:
        return None
    sec = run.trace.op_seconds(KERNEL)
    work = _work(run)
    if sec <= 0 or work is None:
        return None
    share, _ = roofline.share(*work, sec, roofline.peaks(run.device_kind))
    return 100.0 * share


def note(run):
    work = _work(run) or (0.0, 0.0)
    sec = run.trace.op_seconds(KERNEL) if run.trace else 0.0
    _, bound = roofline.share(*work, max(sec, 1e-12),
                              roofline.peaks(run.device_kind))
    return (f"{bound} bound: {work[0]:.6g} FLOP, {work[1]:.6g} B, "
            f"kernel {sec:.6g} s")
