"""Engine loop: time the host blocks in the batched read of a step's
outputs, per engine step in the window, harvest_wait / steps, in ms (the
program's ``harvest_wait_ns`` counter)."""


def read(run):
    steps = run.stats["steps"]
    if not steps or "harvest_wait_ns" not in run.stats:
        return None
    return run.stats["harvest_wait_ns"] / steps / 1e6
