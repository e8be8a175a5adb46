"""Model FLOPs that the window's rounds and prefills need (every live
row's draft passes and target window at its planned length, every admitted
prompt's prefill in both models, attention included; from the shapes and
context lengths the window ran), over window seconds times the chip's bf16
peak, in percent."""

import roofline

LAYER = "model"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    flops = sum(roofline.round_work(run.config, r)["flops"] for r in rounds)
    flops += sum(roofline.prefill_work(run.config, p[3])["flops"]
                 for p in run.window_prefills())
    pk = roofline.peaks(run.device_kind)
    return 100.0 * flops / (run.seconds * run.n_devices * pk["bf16_flops_s"])
