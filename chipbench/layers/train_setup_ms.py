"""``train_setup_ms``: host time a training call spends outside its
crops, steps and scoring, in ms: the self time of the window's
``diva.train``, ``diva.train.init``, ``diva.train.upload`` and
``diva.train.validate`` spans over the count of ``diva.train``."""

CALL = "diva.train"
SPANS = (CALL, "diva.train.init", "diva.train.upload", "diva.train.validate")


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get(CALL)
    if not n:
        return None
    return 1e3 * sum(p["self_s"].get(k, 0.0) for k in SPANS) / n
