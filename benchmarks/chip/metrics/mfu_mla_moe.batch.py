"""Whole step, latent attention + experts: the model operations of the
window's output tokens as a DeepSeek-V2 block computes them
(``chipbench/latent_moe_counts.py``: projections and both absorptions,
attention over each token's context at the latent width, the dense layer,
router, shared and held experts, the unembedding; the draft's forward not
counted) per second, over the chips' bf16 peak from peaks.json, in %."""
from chipbench import latent_moe_counts as lmc


def read(run):
    cfg = run.cell.config
    if not lmc.is_latent_moe(cfg) or not run.window_s:
        return None
    keys = lmc.context_keys(cfg, run.output_tokens, run.output_flops)
    ops = lmc.output_flops(cfg, run.output_tokens, keys)
    return 100.0 * ops / run.window_s / (
        run.chips * run.peak("bf16_flops_per_s"))
