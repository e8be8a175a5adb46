"""Share of its roofline that the ``paged_attention`` kernel reaches in the
traced window: the least time its calls need (attention FLOPs and the K/V
bytes of the context tokens each call reads, from the shapes and context
lengths the window ran: draft passes, J=1 target windows, prefills) over
the kernel's device time, in percent.  A model's attention is counted only
where its architecture does it with this kernel (``ATTENTION_KERNEL``)."""

import arch
import roofline

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = r"paged_attention"


def _work(run):
    t0, t1 = run.trace_window
    mine = {role: arch.of(run.config[role]).ATTENTION_KERNEL
            == "paged_attention" for role in ("target", "draft")}
    flops = nbytes = 0.0
    for r in run.window_rounds(t0, t1):
        w = roofline.round_work(run.config, r)
        if mine["draft"]:
            flops += w["draft_attn"][0]
            nbytes += w["draft_attn"][1]
        if r.J == 1 and mine["target"]:
            flops += w["target_attn"][0]
            nbytes += w["target_attn"][1]
    for p in run.window_prefills(t0, t1):
        w = roofline.prefill_work(run.config, p[3])
        for role in ("target", "draft"):
            if mine[role]:
                flops += w[f"{role}_attn"][0]
                nbytes += w[f"{role}_attn"][1]
    return flops, nbytes


def read(run):
    if run.trace is None:
        return None
    sec = run.trace.op_seconds(KERNEL)
    if sec <= 0:
        return None
    flops, nbytes = _work(run)
    share, _ = roofline.share(flops, nbytes, sec, roofline.peaks(run.device_kind))
    return 100.0 * share


def note(run):
    flops, nbytes = _work(run)
    sec = run.trace.op_seconds(KERNEL) if run.trace else 0.0
    _, bound = roofline.share(flops, nbytes, max(sec, 1e-12),
                              roofline.peaks(run.device_kind))
    return (f"{bound} bound: {flops:.6g} FLOP, {nbytes:.6g} B, "
            f"kernel {sec:.6g} s")
