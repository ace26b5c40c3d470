"""The control, the reference computed with float8 matmuls in the
program's place, fails the limits that ``correct`` holds the cells to;
the reference against itself passes them.  The reference with the
exchange between chips left out fails them too."""

import pytest

from tests import tiny
from chipbench import compare, reference, registry, traffic_gen
from chipbench.archs import dense

SEED = 3_000_000_019


def _batches(traffic):
    import numpy as np
    rng = np.random.default_rng(0)
    out = []
    for lens in traffic_gen.ordered(traffic, SEED)[:3]:
        toks = rng.integers(1, tiny.CONFIG["vocab_size"], sum(lens))
        out.append((toks, lens))
    return out


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  registry.load_benchmark()["workloads"]])
def test_control_fails_each_cells_limits(cell):
    dims = dense.sizes(tiny.CONFIG)
    hp = reference.Hyper.from_traffic(tiny.TRAFFIC)
    batches = _batches(tiny.TRAFFIC)
    ref = dense.run(dims, hp, SEED, batches)
    limits = registry.cell(cell).limits
    ok, _ = compare.judge(compare.numbers(ref, ref), limits)
    assert ok
    ctl = dense.run(dims, hp, SEED, batches, mode="fp8")
    ok, got = compare.judge(compare.numbers(ctl, ref), limits)
    assert not ok, got


def test_exchange_left_out_fails_the_limits():
    """The reference with attention cut at four workers' frames (the
    exchange between chips left out) fails the tiny cell's limits."""
    dims = dense.sizes(tiny.CONFIG)
    hp = reference.Hyper.from_traffic(tiny.TRAFFIC)
    batches = [(toks.reshape(4, -1), lens)
               for toks, lens in _batches(tiny.TRAFFIC)]
    ref = dense.run(dims, hp, SEED, batches)
    cut = dense.run(dims, hp, SEED, batches, fault="no_exchange")
    ok, got = compare.judge(compare.numbers(cut, ref), tiny.LIMITS)
    assert not ok, got
