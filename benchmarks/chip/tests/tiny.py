"""A throwaway cell at a size the CPU runs in seconds: a copy of the
benchmark's files in a temporary directory, plus a tiny configuration,
traffic mix and limits of its own (and, where a test asks, an
architecture or a metric of its own), and a ``BENCHMARK.json`` naming
them.  Nothing of the real benchmark's files is edited to add it."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny.realworld.cpu"
CONFIG = {
    "source": "a tiny dense transformer for CPU tests",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 1, "vocab_size": 256,
    "rope_theta": 10000, "use_qkv_bias": True, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "param_dtype": "bfloat16",
}
TRAFFIC = {
    "driver": "inline_loop",
    "lengths": {"kind": "lognormal", "mean": 512, "sigma": 1.4,
                "min_len": 16, "max_len": 4096},
    "workers": 1, "tokens_per_worker": 512, "block_size": 128,
    "compositions": 4,
    "parallel": {"attention_impl": "fused_xla", "attn_block_q": 64,
                 "attn_block_k": 64, "coalesce": 16, "comm_dtype": "f32",
                 "plan_cache_size": 64, "plan_ahead": True},
    "train": {"lr": 3e-4, "warmup_steps": 2, "total_steps": 10000,
              "weight_decay": 0.1, "grad_clip": 1.0, "b1": 0.9,
              "b2": 0.95},
}
# made-up peaks so that the readers run on the CPU; never reported
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# the tiny cell's own limits (CPU readings: program 3.3e-4 nats,
# 1.9e-3, 2.1e-3; control 5.0e-3 nats, 1.4e-2, 2.0e-2)
LIMITS = {"loss_gap": 2e-3, "grad_norm_gap": 6e-3, "update_norm_gap": 8e-3}


def make(tmp: pathlib.Path, *, metric_source: str | None = None,
         arch_source: str | None = None, traffic: dict | None = None,
         limits: dict | None = None) -> tuple[pathlib.Path, pathlib.Path]:
    """(root, bench_dir) of a copy holding the tiny cell.  With
    ``metric_source`` a new per-layer metric ``tiny_probe`` is added as
    one more file and one more entry; with ``arch_source`` a new
    architecture ``tiny_toy``, which the tiny configuration names;
    ``traffic`` replaces :data:`TRAFFIC`, and the cell asks for as many
    chips as it has workers."""
    bench_dir = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    config, traffic = dict(CONFIG), traffic or TRAFFIC
    if arch_source is not None:
        (bench_dir / "chipbench" / "archs" / "tiny_toy.py").write_text(
            arch_source)
        config["arch"] = "tiny_toy"
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(config))
    traffic_name = f"tiny_512_{traffic['workers']}w"
    (bench_dir / "traffic" / f"{traffic_name}.json").write_text(
        json.dumps(traffic))
    (bench_dir / "limits" / f"{CELL}.json").write_text(
        json.dumps(limits or LIMITS))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny", "source": CONFIG["source"],
        "file": "benchmarks/chip/configs/tiny.json", "reduced": [],
        "why": "CPU tests"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny", "traffic": traffic_name,
        "chips": int(traffic["workers"]), "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    if metric_source is not None:
        (bench_dir / "metrics" / "tiny_probe.py").write_text(metric_source)
        bench["per_layer"].append({
            "name": "tiny_probe", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "driver + planner",
            "moves": "tokens_per_s_per_chip", "workloads": [CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, bench_dir


def run(tmp: pathlib.Path, *, seed: int = 7, seconds: float = 0.5,
        trace: bool = False, **kw) -> dict:
    import time

    from chipbench import harness, registry

    root, bench_dir = make(tmp, **kw)
    cell = registry.cell(CELL, root, bench_dir)
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(),
                            require_chip=False, compile_cache=False,
                            peak=CPU_PEAK, bench_dir=bench_dir)
