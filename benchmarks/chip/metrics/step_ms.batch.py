"""Engine loop: the window's seconds over the engine steps run in it (a
step is one dispatch and harvest of the fused draft + verify program), in
ms."""


def read(run):
    steps = run.stats["steps"]
    return run.window_s / steps * 1e3 if steps else None
