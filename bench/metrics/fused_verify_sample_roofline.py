"""Share of its roofline that the ``fused_verify_sample`` kernel (J=1
accept test and residual sample) reaches in the traced window: the least
time is reading the float32 target logits of every live row's planned
positions (L_k + 1 rows of the vocabulary) once, a memory bound, over the
kernel's device time, in percent."""

import roofline

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = r"fused_verify_sample"


def _bytes(run):
    V = run.config["target"]["vocab_size"]
    rows = sum(roofline.round_work(run.config, r)["verify_positions"]
               for r in run.window_rounds(*run.trace_window) if r.J == 1)
    return 4.0 * V * rows


def read(run):
    if run.trace is None:
        return None
    sec = run.trace.op_seconds(KERNEL)
    if sec <= 0:
        return None
    share, _ = roofline.share(0.0, _bytes(run), sec,
                              roofline.peaks(run.device_kind))
    return 100.0 * share


def note(run):
    sec = run.trace.op_seconds(KERNEL) if run.trace else 0.0
    return f"memory bound: {_bytes(run):.6g} B, kernel {sec:.6g} s"
