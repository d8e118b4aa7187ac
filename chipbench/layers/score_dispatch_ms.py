"""``score_dispatch_ms``: host time of one scoring dispatch (placing
the batch and calling the jitted scorer), in ms: the total time of the
window's ``diva.score.dispatch`` spans over their count."""

SPAN = "diva.score.dispatch"


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get(SPAN)
    if not n:
        return None
    return 1e3 * p["total_s"][SPAN] / n
