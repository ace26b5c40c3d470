"""Share of the traced window in which no op ran on the device (the
union of op intervals, averaged over the chips), in percent."""

from chipbench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace)
                    / run.trace.window_s)
