"""Device time of the draft programs (``draft_step``, ``forest_step``) in
the traced window, per round dispatched in it."""

LAYER = "round steps"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(run):
    if run.trace is None:
        return None
    rounds = run.window_rounds(*run.trace_window)
    sec = run.trace.module_seconds("draft_step", "forest_step")
    return 1e3 * sec / len(rounds) if rounds and sec > 0 else None
