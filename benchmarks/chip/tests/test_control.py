"""The control, the reference computed with float8 matmuls in the
program's place, fails the limits that ``correct`` holds the cells to;
the reference against itself passes them."""

import pytest

from tests import tiny
from chipbench import compare, reference, registry, traffic_gen
from chipbench.model_cfg import Dims

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
    dims = Dims.from_config(tiny.CONFIG)
    hp = reference.Hyper.from_traffic(tiny.TRAFFIC)
    batches = _batches(tiny.TRAFFIC)
    ref = reference.run(dims, hp, SEED, batches)
    limits = registry.cell(cell).limits
    ok, _ = compare.judge(compare.numbers(ref, ref), limits)
    assert ok
    ctl = reference.run(dims, hp, SEED, batches, mode="fp8")
    ok, got = compare.judge(compare.numbers(ctl, ref), limits)
    assert not ok, got
