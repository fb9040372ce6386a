"""Drafting: tokens committed per live row per verify step in the window,
1 + accepted / live row-steps (the scheduler's counters)."""


def read(run):
    live = run.stats["live_steps"]
    return 1.0 + run.stats["accepted"] / live if live else None
