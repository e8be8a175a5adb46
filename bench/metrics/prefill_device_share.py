"""Device time of the prefill programs in the traced window, over the
traced window, in percent.  ``add_streams`` runs one compiled
``prefill_step`` program per prompt bucket (``jit_prefill_step``), which
this reads among every XLA program other than the compiled round steps
(with the small page-table updates and padding of an admission)."""

LAYER = "engine prefill"
SOURCE = "device_trace"
MOVES = "ttft_p50_s"
ROUND_STEPS = ("draft_step", "verify_step", "commit", "forest_step",
               "tree_verify_step", "tree_commit_step")


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    rounds = t.module_seconds(*ROUND_STEPS)
    return 100.0 * (sum(t.modules.values()) - rounds) / t.window_s
