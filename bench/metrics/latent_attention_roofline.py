"""Share of its roofline that the paged latent-attention kernel (MLA in the
absorbed form: the target's J=1 verify windows) reaches in the traced
window: the least time its calls need (per head and context token a dot
product over the latent row and a weighted sum of its latent part, and the
latent rows of each row's context read once, from the shapes and context
lengths the window ran) over the kernel's device time, in percent.  Read
where the target's architecture does its attention with this kernel
(``ATTENTION_KERNEL``); the prefill's expanded attention runs without it."""

import arch
import roofline

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = r"latent_attention"


def _work(run):
    if arch.of(run.config["target"]).ATTENTION_KERNEL != "latent_attention":
        return 0.0, 0.0
    flops = nbytes = 0.0
    for r in run.window_rounds(*run.trace_window):
        if r.J == 1:
            w = roofline.round_work(run.config, r)
            flops += w["target_attn"][0]
            nbytes += w["target_attn"][1]
    return flops, nbytes


def read(run):
    if run.trace is None:
        return None
    sec = run.trace.op_seconds(KERNEL)
    flops, nbytes = _work(run)
    if sec <= 0 or nbytes <= 0:
        return None
    share, _ = roofline.share(flops, nbytes, sec,
                              roofline.peaks(run.device_kind))
    return 100.0 * share


def note(run):
    flops, nbytes = _work(run)
    sec = run.trace.op_seconds(KERNEL) if run.trace else 0.0
    _, bound = roofline.share(flops, nbytes, max(sec, 1e-12),
                              roofline.peaks(run.device_kind))
    return (f"{bound} bound: {flops:.6g} FLOP, {nbytes:.6g} B, "
            f"kernel {sec:.6g} s")
