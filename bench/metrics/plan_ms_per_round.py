"""Host time of the planner (the program's ``cell.plan`` spans: the scheme
choosing each round's draft lengths and bandwidth) in the window, over the
rounds in the window.  Read in traced runs, where the harness installs the
program's tracer after warm-up."""

LAYER = "cell + scheduler"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(run):
    spans = run.spans("cell.plan")
    rounds = run.window_rounds()
    if not spans or not rounds:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1 in spans) / len(rounds)
