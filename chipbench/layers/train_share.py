"""``train_share``: self time of the harness's ``train`` spans as a share
of the traced window, in %: operator training (CloudTrainer.train and
its Adam steps), less the crops it renders and the validation it scores."""

SPAN = "train"


def read(ctx):
    s = ctx["trace"]["self_s"].get(SPAN)
    if s is None:
        return None
    return 100.0 * s / ctx["window_s"]
