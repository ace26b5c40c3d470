"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Nothing here knows a particular cell.  A cell names a configuration (a
file that the configuration's entry points to) and a traffic mix
(``traffic/<name>.json``); the traffic names its driver
(``drivers/<name>.py``); the configuration names its architecture
(``chipbench/archs/<name>.py``, by its ``"arch"`` key, ``dense`` where it
has none); a metric is ``metrics/<name>.py``; the limits that decide
``correct`` are ``limits/<cell>.json``.  A new cell, metric or
architecture is new files and new entries, never an edit."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import types

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file, as run
    arch: types.ModuleType  # chipbench/archs/<config's "arch">.py
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list        # metric entries of BENCHMARK.json
    per_layer: list

    def metrics(self, trace: bool) -> list:
        return self.per_layer if trace else self.end_to_end


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell(name: str, root: pathlib.Path = ROOT,
         bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    root, bench_dir = pathlib.Path(root), pathlib.Path(bench_dir)
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config_name=conf["name"],
        config=config, arch=arch(config.get("arch", "dense"), bench_dir),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads(
            (bench_dir / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _module(path: pathlib.Path) -> types.ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric(name: str, bench_dir: pathlib.Path = BENCH_DIR
           ) -> types.ModuleType:
    """``metrics/<name>.py``: exposes ``read(run) -> float | None``."""
    return _module(pathlib.Path(bench_dir) / "metrics" / f"{name}.py")


def arch(name: str, bench_dir: pathlib.Path = BENCH_DIR
         ) -> types.ModuleType:
    """``chipbench/archs/<name>.py``: what :mod:`chipbench.archs` lists."""
    return _module(pathlib.Path(bench_dir) / "chipbench" / "archs"
                   / f"{name}.py")


def driver(name: str, bench_dir: pathlib.Path = BENCH_DIR
           ) -> types.ModuleType:
    """``drivers/<name>.py``: exposes ``Driver(cell, dims, seed, hp)``
    (``dims`` the architecture's sizes) with ``step()``,
    ``compositions``, ``params``, ``opt`` and ``free()``."""
    return _module(pathlib.Path(bench_dir) / "drivers" / f"{name}.py")
