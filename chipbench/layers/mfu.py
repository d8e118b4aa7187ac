"""``mfu``: operations the window's scoring (forward passes of the real
frames) and training (forward and backward of every sample of every
Adam step) required, counted from shapes by ``work.py``, over the
window's seconds times the chip's peak FLOP/s, in %."""
import work


def read(ctx):
    flops = sum(n * work.forward_flops(s)
                for s, n in ctx["score_frames"].items())
    flops += sum(n * work.train_flops(s)
                 for s, n in ctx["train_samples"].items())
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx["window_s"] * ctx["peak"]["flops_per_s"])
