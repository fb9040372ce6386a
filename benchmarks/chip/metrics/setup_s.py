"""Set-up seconds: process start to the window (JAX, weights, engine,
warm-up compiles, traffic, and what the traffic needs before its window)."""


def read(run):
    return run.setup_s
