"""``adam_step_host_ms``: host time of one Adam iteration of operator
training (its gather and its ``_adam_step`` dispatch), in ms: the total
time of the window's ``diva.train.step`` spans over their count."""

SPAN = "diva.train.step"


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get(SPAN)
    if not n:
        return None
    return 1e3 * p["total_s"][SPAN] / n
