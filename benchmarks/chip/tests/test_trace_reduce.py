"""The trace reduction against a small trace recorded on a TPU v5 lite
(a slice of a traced window of ``stablelm.realworld.1chip``), checked by
a brute-force count over a 1 us timeline."""

import json
import pathlib

import numpy as np
import pytest

from tests import tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import trace_reduce

DATA = pathlib.Path(__file__).with_name("data") / "trace_small.json"
KERNELS = ("fused_flash_fwd", "fused_flash_bwd_dq", "fused_flash_bwd_dkv")


@pytest.fixture(scope="module")
def view():
    return trace_reduce.TraceView.from_json(json.loads(DATA.read_text()))


def _timeline(view, events):
    lo, hi = view.window
    n = int(np.ceil((hi - lo) / 1000.0))
    on = np.zeros(n, bool)
    for e in events:
        a = int(np.floor((max(e.start_ns, lo) - lo) / 1000.0))
        b = int(np.ceil((min(e.end_ns, hi) - lo) / 1000.0))
        if b > a:
            on[a:b] = True
    return on


def test_recorded_trace_has_what_the_metrics_read(view):
    assert len(view.devices) == 1
    ops = next(iter(view.devices.values()))
    assert len(ops) > 100
    assert any(trace_reduce.names_matcher(KERNELS)(e) for e in ops)
    assert {s.name for s in view.host} >= {"bench.dispatch", "bench.sync"}


def test_busy_and_idle_against_a_timeline(view):
    ops = next(iter(view.devices.values()))
    on = _timeline(view, ops)
    busy = trace_reduce.busy_s(view)
    # the timeline rounds each interval out to whole microseconds
    assert busy <= on.sum() * 1e-6 + 1e-9
    assert busy >= on.sum() * 1e-6 - 2e-6 * len(ops)
    assert 0 < busy < view.window_s


def test_kernel_seconds_against_a_timeline(view):
    ops = [e for e in next(iter(view.devices.values()))
           if trace_reduce.names_matcher(KERNELS)(e)]
    assert ops
    on = _timeline(view, ops)
    got = trace_reduce.matching_s(view, trace_reduce.names_matcher(KERNELS))
    assert abs(got - on.sum() * 1e-6) <= 2e-6 * len(ops)
    # kernels never overlap one another on one device: the union is
    # their summed durations
    lo, hi = view.window
    total = sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in ops
                if min(e.end_ns, hi) > max(e.start_ns, lo)) * 1e-9
    assert got == pytest.approx(total, rel=1e-9)


def test_breakdown(view):
    top = trace_reduce.top_ops(view)
    assert 0 < len(top) <= 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    gaps = trace_reduce.idle_gaps(view)
    assert 0 < len(gaps) <= 10
    lens = [s for _, s in gaps]
    assert lens == sorted(lens, reverse=True)
    ops = next(iter(view.devices.values()))
    idle = ~_timeline(view, ops)
    # the longest gap is no longer than the idle time there is
    assert lens[0] <= idle.sum() * 1e-6 + 1e-6
    names = {s.name for s in view.host} | {"none"}
    assert all(n in names for n, _ in gaps)


def test_synthetic_gaps_are_attributed():
    e = trace_reduce.Event
    v = trace_reduce.TraceView(
        devices={"/device:TPU:0": [e("a", 0, 10), e("b", 5, 10),
                                   e("c", 40, 10), e("d", 95, 10)]},
        host=[e("bench.plan", 14, 20), e("bench.sync", 50, 45)],
        window=(0, 100))
    assert trace_reduce.busy_s(v) == pytest.approx(30e-9)
    gaps = trace_reduce.idle_gaps(v)
    assert [n for n, _ in gaps] == ["bench.sync", "bench.plan"]
    assert [s for _, s in gaps] == pytest.approx([45e-9, 25e-9])
    assert trace_reduce.top_ops(v)[0] == ["a", pytest.approx(10e-9)]


def test_synthetic_exposed_collectives():
    """One P2P op wholly hidden under compute, one partly, one alone; a
    second device with one P2P op alone.  The reader averages over the
    devices and divides by the steps."""
    import types

    from chipbench import registry

    e = trace_reduce.Event
    v = trace_reduce.TraceView(
        devices={
            "/device:TPU:0": [
                e("fusion.1", 0, 100),
                e("collective-permute-done.1", 20, 20),     # hidden
                e("collective-permute-start.2", 90, 30),    # 20 exposed
                e("collective-permute-done.3", 150, 20),    # alone
                e("all-reduce.4", 200, 30),                 # not P2P
            ],
            "/device:TPU:1": [e("fusion.1", 0, 100),
                              e("collective-permute-done.1", 100, 10)],
        },
        host=[], window=(0, 300))
    p2p = registry.metric("p2p_exposed_ms")
    assert trace_reduce.exposed_s(v, p2p.P2P) == pytest.approx(
        (40 + 10) / 2 * 1e-9)
    assert trace_reduce.matching_s(v, p2p.P2P) == pytest.approx(
        (70 + 10) / 2 * 1e-9)
    run = types.SimpleNamespace(trace=v, steps=[[8], [8]])
    assert p2p.read(run) == pytest.approx(25e-9 / 2 * 1e3)
    # nothing to read: no trace, or no such op ran
    assert p2p.read(types.SimpleNamespace(trace=None, steps=[[8]])) is None
    quiet = trace_reduce.TraceView(
        devices={"/device:TPU:0": [e("fusion.1", 0, 100),
                                   e("all-reduce.4", 200, 30)]},
        host=[], window=(0, 300))
    assert p2p.read(types.SimpleNamespace(trace=quiet, steps=[[8]])) is None


def test_op_name_from_hlo_text():
    text = ("%fusion.12 = bf16[2240,5632]{1,0} fusion(%a, %b), kind=kCustom,"
            " calls=%all-reduce-scatter.3, backend_config={}")
    assert trace_reduce.op_name(text) == "fusion.12"
    assert trace_reduce.op_name("%add.1 = f32[] add(%x, %y)") == "add.1"
