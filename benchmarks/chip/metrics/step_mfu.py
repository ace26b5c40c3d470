"""Model FLOPs of the window over the device's busy time (not the
window's) x chips x peak, in percent: the step's share of the peak
while the device runs, with host idle taken out."""

from chipbench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    busy = trace_reduce.busy_s(run.trace)
    if busy <= 0:
        return None
    work = sum(run.cell.arch.model_flops(run.dims, s) for s in run.steps)
    return 100.0 * work / (busy * run.chips * run.peak["bf16_flops_per_s"])
