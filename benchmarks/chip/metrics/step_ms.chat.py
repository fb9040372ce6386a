"""Engine loop under open-loop arrivals: the window's seconds over the
engine steps run in it, in ms (idle waits for arrivals included)."""


def read(run):
    steps = run.stats["steps"]
    return run.window_s / steps * 1e3 if steps else None
