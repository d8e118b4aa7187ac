"""``render_share``: self time of the harness's ``render`` spans as a share
of the traced window, in %: the frame path (Video.render_frames under
the frame cache)."""

SPAN = "render"


def read(ctx):
    s = ctx["trace"]["self_s"].get(SPAN)
    if s is None:
        return None
    return 100.0 * s / ctx["window_s"]
