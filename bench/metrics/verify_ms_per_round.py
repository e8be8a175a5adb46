"""Device time of the target-pass programs (``verify_step``,
``tree_verify_step``) in the traced window, per round dispatched in it."""

LAYER = "round steps"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(run):
    if run.trace is None:
        return None
    rounds = run.window_rounds(*run.trace_window)
    sec = run.trace.module_seconds("verify_step", "tree_verify_step")
    return 1e3 * sec / len(rounds) if rounds and sec > 0 else None
