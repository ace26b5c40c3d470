"""A cell, a traffic mix, a metric and an architecture are added by
adding files and entries, without editing a file the benchmark already
has."""

import json

import pytest

from tests import tiny
from chipbench import registry, traffic_gen

PROBE = '''"""Throwaway metric: steps in the window."""


def read(run):
    return float(len(run.steps))
'''


TOY_ARCH = '''"""Throwaway architecture: the dense decoder, whose FLOP
count also counts its RMSNorms (four operations a width entry: square,
mean, scale, weight; two a layer and the final one); each hook notes
its call."""

from chipbench.archs import dense

CALLS = set()


def sizes(config):
    CALLS.add("sizes")
    return dense.sizes(config)


def maker(dims, out_shardings=None):
    CALLS.add("maker")
    return dense.maker(dims, out_shardings)


def run(dims, hp, seed, batches, mode="f32", fault=None):
    CALLS.add("run")
    return dense.run(dims, hp, seed, batches, mode, fault)


def model_flops(dims, seqlens):
    CALLS.add("model_flops")
    norms = (2 * dims.n_layers + 1) * 4 * dims.d_model * sum(seqlens)
    return dense.model_flops(dims, seqlens) + norms


def attention_least_seconds(dims, seqlens, peak):
    CALLS.add("attention_least_seconds")
    return dense.attention_least_seconds(dims, seqlens, peak)
'''


def test_registry_finds_the_real_cells():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        cell = registry.cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.traffic["driver"]
        assert set(cell.limits) >= {"loss_gap", "grad_norm_gap",
                                    "update_norm_gap"}
        for m in cell.end_to_end + cell.per_layer:
            assert hasattr(registry.metric(m["name"]), "read")
        assert hasattr(registry.driver(cell.traffic["driver"]), "Driver")
    with pytest.raises(KeyError):
        registry.cell("no.such.cell")


def test_added_cell_and_metric_run_without_edits(tmp_path):
    before = {p: p.read_bytes() for p in tiny.BENCH.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    out = tiny.run(tmp_path, trace=True, metric_source=PROBE)
    assert out["correct"], out["compared"]
    assert out["metrics"]["tiny_probe"]["value"] == out["attempted"]
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert list(out)[-1] == "compared"
    json.dumps(out)
    after = {p: p.read_bytes() for p in tiny.BENCH.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert before == after


def test_trace_passes_limit_the_traced_steps(tmp_path):
    """A traffic that names ``trace_passes`` traces that many passes of
    the window; the window runs on for its whole length untraced, and
    the per-layer readers see the traced steps."""
    traffic = dict(tiny.TRAFFIC, trace_passes=1)
    out = tiny.run(tmp_path, trace=True, seconds=2.0, traffic=traffic,
                   metric_source=PROBE)
    assert out["correct"], out["compared"]
    assert out["metrics"]["tiny_probe"]["value"] == traffic["compositions"]
    assert out["attempted"] > traffic["compositions"]
    assert out["device"]["window_s"] < 2.0


def test_added_architecture_runs_without_edits(tmp_path):
    import sys

    out = tiny.run(tmp_path, arch_source=TOY_ARCH)
    assert out["correct"], out["compared"]
    toy = sys.modules["chipbench_archs_tiny_toy"]
    assert toy.CALLS >= {"sizes", "maker", "run", "model_flops"}
    # mfu over the rate is the toy's FLOPs a token over the peak: the
    # window runs whole passes over the compositions
    dims = toy.sizes(tiny.CONFIG)
    comps = traffic_gen.compositions(tiny.TRAFFIC)
    per_token = (sum(toy.model_flops(dims, c) for c in comps)
                 / sum(sum(c) for c in comps))
    m = out["metrics"]
    assert m["mfu"]["value"] / m["tokens_per_s_per_chip"]["value"] == (
        pytest.approx(100 * per_token / tiny.CPU_PEAK["bf16_flops_per_s"],
                      rel=1e-9))
    bench_dir = tmp_path / "benchmarks" / "chip"
    for p in tiny.BENCH.rglob("*"):
        if p.is_file() and not {"__pycache__", "tests"} & set(p.parts):
            assert (bench_dir / p.relative_to(tiny.BENCH)).read_bytes() \
                == p.read_bytes(), p
