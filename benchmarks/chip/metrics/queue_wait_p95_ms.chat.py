"""Scheduler admission: 95th percentile over all requests due in the
window of the program's own submit-to-admission wait
(``Completion.queue_wait``), in ms."""
from chipbench.stats import percentile


def read(run):
    return percentile([r.queue_wait * 1e3 for r in run.counted
                       if r.queue_wait is not None], 95)
