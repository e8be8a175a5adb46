"""Held experts that got at least one token, over the held experts, per MoE
layer and target pass (verify and prefill), over the window, in percent:
the program's counters ``engine.moe_experts_touched`` over
``engine.moe_layer_passes`` times the experts held here.  Near 100% every
pass reads every held expert's weights."""

LAYER = "model"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(run):
    touched = run.counters.get("engine.moe_experts_touched")
    passes = run.counters.get("engine.moe_layer_passes")
    if not touched or not passes:
        return None
    return 100.0 * touched / (passes * run.config["target"]["n_routed_experts"])
