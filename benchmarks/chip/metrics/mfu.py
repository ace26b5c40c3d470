"""The whole step's share of the chips' bf16 peak: model FLOPs of every
step in the window (the architecture's count from the compositions'
document lengths: active weights and causal attention; recompute not
counted) over window seconds x chips x peak, in percent."""


def read(run):
    work = sum(run.cell.arch.model_flops(run.dims, s) for s in run.steps)
    return (100.0 * work / (run.window_s * run.chips
                            * run.peak["bf16_flops_per_s"]))
