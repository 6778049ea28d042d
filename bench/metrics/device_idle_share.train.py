"""Device: share of the traced window in which no operation ran on the
chip, mean over chips. Training cells. Moves `train_tokens_per_s`."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else 100.0 * tr.idle_share()
