"""Run one cell of the FCP trainer's chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  Prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, last, the numbers
compared with the reference beside their limits (also the last lines of
standard error).  Exits non-zero, printing no result, without a TPU or
with fewer chips than the cell asks for, and outside a checkout of the
program.  JAX's compile cache is the program's (``.jax_cache/`` at the
checkout root, or ``$JAX_COMPILATION_CACHE_DIR``); ``LIBTPU_INIT_ARGS``
is left as the machine sets it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"run.py: no program under {ROOT / 'src'}: run from a "
                 f"checkout of the repository")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from chipbench import harness, registry

    cell = registry.cell(args.workload, ROOT)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    print(json.dumps(harness.to_json_safe(out)), flush=True)


if __name__ == "__main__":
    main()
