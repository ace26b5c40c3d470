"""The four-worker driver (``drivers/supervised_loop.py``) at the tiny
size on four host devices, through ``harness.run_cell``: the unbroken run
comes out correct, and a run with the timed path broken underneath comes
out not correct, once for each fault a four-chip training cell can
have."""

import json
import os
import subprocess
import sys

import pytest

from tests import tiny
from tests.supervised_runs import VARIANTS


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-m", "tests.supervised_runs",
         str(tmp_path_factory.mktemp("supervised"))],
        cwd=tiny.BENCH, env=env, capture_output=True, text=True,
        timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [str(v) for v in VARIANTS])
def test_supervised_fault_is_caught(outcomes, fault):
    out = outcomes[fault]
    assert out["device"]["count"] == 4
    assert out["attempted"] >= 4
    assert out["correct"] is (fault == "None"), out["compared"]
