"""95th percentile over all requests due in the window of the time from
the request's due time to its first committed token, in ms."""
from chipbench.stats import percentile


def read(run):
    return percentile([(r.first_token - r.due) * 1e3 for r in run.counted
                       if r.first_token is not None], 95)
