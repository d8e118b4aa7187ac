"""``conv_scorer_roofline``: the least time the chip could take for the
conv layers of the real frames the window scored (``work.py``: the
larger of operations over peak FLOP/s and bytes over peak bytes/s, per
signature), over the summed device time of the kernel's events, in %.
"""
import work


def read(ctx):
    kernel_s = ctx["trace"]["kernel_s"]
    if kernel_s <= 0 or not ctx["score_frames"]:
        return None
    least, _bound = work.conv_min_seconds(ctx["score_frames"], ctx["peak"])
    return 100.0 * least / kernel_s
