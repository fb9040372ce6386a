"""Kernels: device time of the Pallas attention kernels (``tpu_custom_call``
ops) over the device's busy time in the traced window, in %."""


def read(run):
    t = run.trace
    return 100.0 * t["pallas_s"] / t["busy_s"] if t and t["busy_s"] else None
