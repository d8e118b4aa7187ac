"""``frames_per_dispatch``: real frames scored per scoring dispatch over
the window, from the runtime's ``dispatch_stats`` (all three layers:
small, bucketed, superbatch)."""


def read(ctx):
    d = ctx["dispatch"]
    if not d.get("calls"):
        return None
    return d["frames_scored"] / d["calls"]
