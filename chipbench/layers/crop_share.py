"""``crop_share``: self time of the program's ``diva.frames.crop`` spans
(``FrameBank`` cropping and resizing frames on the host) as a share of
the traced window, in %."""

SPAN = "diva.frames.crop"


def read(ctx):
    s = ctx["program"]["self_s"].get(SPAN)
    if s is None:
        return None
    return 100.0 * s / ctx["window_s"]
