"""Whole step: model operations of the window's output tokens (projections,
unembedding and attention over each token's context; the draft's forward
not counted) per second, over the chips' bf16 peak from peaks.json, in %."""


def read(run):
    return 100.0 * run.output_flops / run.window_s / (
        run.chips * run.peak("bf16_flops_per_s"))
