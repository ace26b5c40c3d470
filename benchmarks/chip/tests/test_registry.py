"""A cell, a traffic mix and a metric are added by adding files and
entries, without editing a file the benchmark already has."""

import json

import pytest

from tests import tiny
from chipbench import registry

PROBE = '''"""Throwaway metric: steps in the window."""


def read(run):
    return float(len(run.steps))
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
