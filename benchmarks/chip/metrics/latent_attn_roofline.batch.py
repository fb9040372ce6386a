"""Kernels: the latent attention kernel's share of its HBM roofline, the
bytes its calls move in the window (``latent_moe_counts.kernel_bytes``:
each row's context once a call, estimated from the window's context keys
over its mean accepted length, plus queries and outputs) over the
kernel's device time, scaled from the traced tail to the window, and the
chip's HBM bandwidth from peaks.json, in %. The time is the self time of
the trace's ops named ``paged_latent_attention`` (draft and target calls)."""
from chipbench import latent_moe_counts as lmc

KERNEL = "paged_latent_attention"


def read(run):
    t, cfg = run.trace, run.cell.config
    if not t or not t.get("window_s") or not lmc.is_latent_moe(cfg):
        return None
    secs = sum(s for name, s in t["device_ops"] if name.startswith(KERNEL))
    live = run.stats["live_steps"]
    if not secs or not live:
        return None
    mean_accepted = 1.0 + run.stats["accepted"] / live
    keys = lmc.context_keys(cfg, run.output_tokens, run.output_flops)
    moved = lmc.kernel_bytes(cfg, keys, mean_accepted, run.stats["steps"],
                             run.cell.engine["max_batch"],
                             run.cell.engine["k"])
    kernel_s = secs * run.window_s / t["window_s"]
    return 100.0 * moved / kernel_s / (run.chips * run.peak(
        "hbm_bytes_per_s"))
