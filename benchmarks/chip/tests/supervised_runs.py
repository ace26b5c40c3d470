"""Runs the tiny cell through ``drivers/supervised_loop.py`` on four host
devices, once unbroken and once for each fault a four-chip training
cell can have, and prints ``{variant: result line}`` as JSON.  A process
of its own: ``XLA_FLAGS`` must give the CPU four devices before JAX is
imported, so ``test_supervised.py`` starts it as

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m tests.supervised_runs <tmp dir>
"""

from __future__ import annotations

import contextlib
import json
import pathlib
import sys
from unittest import mock

from tests import tiny

TRAFFIC = dict(tiny.TRAFFIC, driver="supervised_loop", workers=4)
VARIANTS = (None, "state_unchanged", "half_batch", "loss_altered",
            "exchange_left_out")


def _broken(fault):
    """The timed path with ``fault`` planted under it."""
    from repro.launch import train
    from repro.runtime import wire
    from tests.test_faults import FAULTS

    if fault is None:
        return contextlib.nullcontext()
    if fault == "exchange_left_out":
        # every worker keeps what it would have sent: no block of Q, K,
        # V or O, and no gradient, crosses between chips
        return mock.patch.object(wire, "ship", lambda x, *a, **k: x)
    orig = train.build_train_step
    return mock.patch.object(
        train, "build_train_step",
        lambda *a, **k: FAULTS[fault](orig(*a, **k)))


def main(tmp: str) -> None:
    out = {}
    for i, fault in enumerate(VARIANTS):
        with _broken(fault):
            out[str(fault)] = tiny.run(pathlib.Path(tmp) / str(i),
                                       traffic=TRAFFIC, seconds=0.2)
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
