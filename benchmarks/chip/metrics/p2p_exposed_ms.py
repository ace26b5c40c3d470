"""Device time per step, in ms, of FCP's point-to-point transfers (the
executor's ``collective-permute`` start and done ops) during which no
other op ran on the same chip, averaged over the chips: the part of the
P2P rounds and reshuffles that compute does not hide.  Nothing to read
where no such op ran."""

from chipbench import trace_reduce

P2P = trace_reduce.prefix_matcher(("collective-permute",))


def read(run):
    if run.trace is None or trace_reduce.matching_s(run.trace, P2P) <= 0:
        return None
    return trace_reduce.exposed_s(run.trace, P2P) / len(run.steps) * 1e3
