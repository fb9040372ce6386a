"""Kernels: the share of the paged attention kernels' grid that covers a
live page in the window, kv_pages_live / kv_pages_grid (the program's
counters from its host mirrors), in %."""


def read(run):
    grid = run.stats.get("kv_pages_grid")
    if not grid or "kv_pages_live" not in run.stats:
        return None
    return 100.0 * run.stats["kv_pages_live"] / grid
