"""``sched_self_share``: self time of the harness's ``run`` spans as a
share of the traced window, in %: the scheduler's own loop:
FleetService.run less the train, render, score and verify spans inside
it."""

SPAN = "run"


def read(ctx):
    s = ctx["trace"]["self_s"].get(SPAN)
    if s is None:
        return None
    return 100.0 * s / ctx["window_s"]
