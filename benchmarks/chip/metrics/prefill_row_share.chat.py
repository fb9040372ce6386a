"""Chunked prefill: the share of row-steps in the window that consumed a
prompt chunk, prefill_chunks / (prefill_chunks + live row-steps), in %."""


def read(run):
    chunks, live = run.stats["prefill_chunks"], run.stats["live_steps"]
    return 100.0 * chunks / (chunks + live) if chunks + live else None
