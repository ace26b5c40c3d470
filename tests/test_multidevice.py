"""Wrapper launching multi-device tests in subprocesses.

Host-platform device count must be set before jax initializes, and the
main test process must keep seeing 1 device (per repo policy), so each
multi-device scenario runs as a separate process with its own XLA_FLAGS.
"""

import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).parent
SRC = str(HERE.parent / "src")


# Each slow runner's one-case ``tier1`` grid runs in tier-1; its
# ``full`` grid is the ``slow`` case (``pytest -m slow``).
GRIDS = pytest.mark.parametrize(
    "grid", ["tier1", pytest.param("full", marks=pytest.mark.slow)])
TIMEOUT = {"tier1": 300, "full": 1800}


def _run(script: str, grid: str = "tier1", devices: int = 8):
    # a runner with one grid (decode, serving) ignores the argument
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"       # keep a timed-out run's output
    cmd = [sys.executable, str(HERE / "multidevice" / script), grid]
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=TIMEOUT[grid])
    except subprocess.TimeoutExpired as e:
        raise AssertionError(
            f"{script} {grid} timed out after {e.timeout} s:\n"
            f"STDOUT:\n{_text(e.stdout)[-4000:]}\n"
            f"STDERR:\n{_text(e.stderr)[-4000:]}") from None
    if proc.returncode != 0:
        raise AssertionError(
            f"{script} {grid} failed:\nSTDOUT:\n{proc.stdout[-4000:]}\n"
            f"STDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout


def _text(out) -> str:
    """Partial output of a timed-out run (bytes, even in text mode)."""
    if isinstance(out, bytes):
        return out.decode(errors="replace")
    return out or ""


@GRIDS
def test_fcp_executor_multidevice(grid):
    # the coalesced long-tailed batch vs the oracle (tier1); the
    # coalescer-equivalence runs and 5 more layouts (full)
    out = _run("run_fcp_executor.py", grid)
    assert "ALL MULTIDEVICE EXECUTOR CASES PASSED" in out


@GRIDS
def test_fused_executor_multidevice(grid):
    # fused-vs-per-step equivalence (outputs + grads, coalesce sweep),
    # launch accounting, and the fused Pallas path in interpret mode
    out = _run("run_fused_executor.py", grid)
    assert "ALL FUSED EXECUTOR CASES PASSED" in out


@GRIDS
def test_masked_executor_multidevice(grid):
    # MaskSpec-driven schedules (sliding-window / chunked / full) and
    # mixed per-layer-group chains vs the dense single-device oracle,
    # outputs + grads <= 1e-6, plus the swa-ships-fewer-edges assertion
    out = _run("run_masked_executor.py", grid)
    assert "ALL MASKED EXECUTOR CASES PASSED" in out


@GRIDS
def test_wire_executor_multidevice(grid):
    # quantized wire formats: ship(f32) bit-exact with raw ppermute,
    # bf16/int8 outputs + grads vs the f32 wire within documented
    # tolerances (causal / swa / mixed layer groups, per-step + fused),
    # and the attn_out_bf16 restore-cast parity
    out = _run("run_wire_executor.py", grid)
    assert "ALL WIRE EXECUTOR CASES PASSED" in out


@GRIDS
def test_overlap_executor_multidevice(grid):
    # double-buffered rounds: overlap on/off bitwise-equal forward
    # outputs, loss and dq under the f32 wire (coalesce 1/4/16,
    # causal + swa, per-step + fused), dk/dv <= 1e-6 (association
    # order differs, see docs/overlap.md), plus fcp_reshuffle
    # round-trip identity and sched-layout attention parity for the
    # layer-pipelined path
    out = _run("run_overlap_executor.py", grid)
    assert "ALL OVERLAP EXECUTOR CASES PASSED" in out


@GRIDS
def test_fault_drill_multidevice(grid):
    # fault-tolerance drill: mid-step worker loss -> survivor replan +
    # checkpoint restore + deterministic replay (post-recovery
    # loss/gnorm <= 1e-6 vs an uninterrupted survivor run), and a
    # 2x-slow worker demoted by the closed health loop within the
    # hysteresis window with plan-cache discipline intact
    out = _run("run_fault_drill.py", grid)
    assert "ALL FAULT DRILL CASES PASSED" in out


def test_cp_decode_multidevice():
    out = _run("run_decode.py")
    assert "ALL MULTIDEVICE DECODE CASES PASSED" in out


def test_serving_multidevice():
    # continuous-batching FCP serving: zero recompiles after warmup,
    # plan-cache hit on every prefill batch, one prefill call per
    # prompt, fcp == dense tokens
    out = _run("run_serve.py")
    assert "ALL MULTIDEVICE SERVING CASES PASSED" in out


@GRIDS
def test_plan_cache_executor_multidevice(grid):
    # amortized planning: cached-vs-uncached executor equivalence
    # (outputs + grads <= 1e-6), >= warmup hit rate, zero recompiles
    out = _run("run_plan_cache.py", grid)
    assert "ALL PLAN CACHE EXECUTOR CASES PASSED" in out
