"""``idle_share``: the share of the traced window in which no operation
ran on the device (1 - union of device-op intervals / window), in %."""


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ctx["window_s"])
