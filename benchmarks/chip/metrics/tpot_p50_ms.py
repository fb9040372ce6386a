"""Median over all requests due in the window of the time per output
token after the first: (done - first token) / (generated - 1), in ms."""
from chipbench.stats import percentile


def read(run):
    return percentile([(r.done - r.first_token) / (r.generated - 1) * 1e3
                       for r in run.counted
                       if r.done is not None and r.generated > 1], 50)
