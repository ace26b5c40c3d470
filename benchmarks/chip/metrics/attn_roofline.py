"""Least time the attention work of the window's compositions needs (the
larger of FLOPs over peak and minimal Q/K/V/O and gradient bytes over
peak bandwidth), over the fused kernels' device time, in percent.  The
work comes from document lengths, not from visited tiles.  On several
chips the least time is each chip's share of it and the kernel time is
averaged over the chips' planes, so the share reads the same work
whatever worker runs it."""

from chipbench import trace_reduce

KERNELS = ("fused_flash_fwd", "fused_flash_bwd_dq", "fused_flash_bwd_dkv")


def read(run):
    if run.trace is None:
        return None
    s = trace_reduce.matching_s(run.trace,
                                trace_reduce.names_matcher(KERNELS))
    if s <= 0:
        return None
    arch = run.cell.arch
    least = sum(arch.attention_least_seconds(run.dims, q, run.peak)[0]
                for q in run.steps) / run.chips
    return 100.0 * least / s
