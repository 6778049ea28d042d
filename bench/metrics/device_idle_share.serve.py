"""Device: share of the traced window in which no operation ran on the
chip (1 - union of operation intervals / window), mean over chips.
Serving cells. Moves `serve_p95_ms`: a request waits while the chip
idles."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else 100.0 * tr.idle_share()
