"""``verify_share``: self time of the harness's ``verify`` spans as a share
of the traced window, in %: the oracle service (OracleService submit,
step, complete, flush)."""

SPAN = "verify"


def read(ctx):
    s = ctx["trace"]["self_s"].get(SPAN)
    if s is None:
        return None
    return 100.0 * s / ctx["window_s"]
