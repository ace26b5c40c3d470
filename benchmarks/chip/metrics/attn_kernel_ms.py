"""Device time per step of the fused FCP attention kernels, forward
(with its recompute under remat), dQ and dK/dV, as the trace names
them (``pallas_call`` names of ``kernels/flash_attention.py``), in ms."""

from chipbench import trace_reduce

KERNELS = ("fused_flash_fwd", "fused_flash_bwd_dq", "fused_flash_bwd_dkv")


def read(run):
    if run.trace is None:
        return None
    s = trace_reduce.matching_s(run.trace,
                                trace_reduce.names_matcher(KERNELS))
    return s / len(run.steps) * 1e3 if s > 0 else None
