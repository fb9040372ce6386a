"""Output tokens per second: every token committed in the window (requests
still running at its end included, nothing past a request's max_new), over
the window's seconds on the harness clock."""


def read(run):
    return run.output_tokens / run.window_s
