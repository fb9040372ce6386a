"""Scheduler: host time of the serving loop's phases per engine step in
the window, (admit + sync_tables + dispatch + harvest) / steps, in ms (the
program's ``*_ns`` counters; the blocking wait for the device is not in
it)."""

PHASES = ("admit_ns", "sync_tables_ns", "dispatch_ns", "harvest_ns")


def read(run):
    steps = run.stats["steps"]
    if not steps or any(k not in run.stats for k in PHASES):
        return None
    return sum(run.stats[k] for k in PHASES) / steps / 1e6
