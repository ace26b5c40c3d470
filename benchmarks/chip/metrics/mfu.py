"""The whole step's share of the chips' bf16 peak: model FLOPs of every
step in the window (6 x matmul parameters x tokens, plus causal
attention from the compositions' document lengths; recompute not
counted) over window seconds x chips x peak, in percent."""

from chipbench import flops


def read(run):
    work = sum(flops.model_flops(run.dims, s) for s in run.steps)
    return (100.0 * work / (run.window_s * run.chips
                            * run.peak["bf16_flops_per_s"]))
