"""Device under open-loop arrivals: the share of the traced window in
which no operation ran on the chip, 1 - busy / window, in %."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
