"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 11 12 ... --controls 3 [--out FILE]

For each seed: the program's first three steps through the timed path
(set-up as a run makes it) against the reference, the three numbers of
:mod:`chipbench.compare`.  For the first ``--controls`` seeds also: the
control (the reference computed with float8 matmuls, put in the
program's place), the half-batch fault (the reference with the second
half of the tokens left out of the loss) and the altered-loss fault
(the reference reporting each loss 1% high) against the reference, and
where the cell has several workers the exchange left out (the reference
with attention cut at the workers' frames).
A state left unchanged reads 1 on both norm gaps by construction and
is not run.  One JSON line per reading goes to ``--out`` and to
standard output.
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
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]

    import jax

    from chipbench import compare, harness, reference, registry

    cell = registry.cell(args.workload, ROOT)
    harness.check_devices(cell.chips, require_chip=True)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dims = cell.arch.sizes(cell.config)
    hp = reference.Hyper.from_traffic(cell.traffic)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(harness.to_json_safe(rec))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        drv = registry.driver(cell.traffic["driver"]).Driver(
            cell, dims, seed, hp)
        first, prog = harness.warm_up(drv, cell.arch, dims, hp, seed)
        checked = [(s.tokens, s.seqlens)
                   for s in first[:harness.CHECK_STEPS]]
        drv.free()
        del drv
        t1 = time.perf_counter()
        ref = cell.arch.run(dims, hp, seed, checked)
        t2 = time.perf_counter()
        emit({"cell": cell.name, "seed": seed, "kind": "program",
              "numbers": compare.numbers(prog, ref),
              "losses": prog.losses, "ref_losses": ref.losses,
              "setup_s": t1 - t0, "reference_s": t2 - t1})
        if i < args.controls:
            kinds = [("control_fp8", {"mode": "fp8"}),
                     ("fault_half_batch", {"fault": "half_batch"}),
                     ("fault_loss_altered", {"fault": "loss_altered"})]
            if int(cell.traffic["workers"]) > 1:
                kinds.append(("fault_no_exchange", {"fault": "no_exchange"}))
            for kind, kw in kinds:
                other = cell.arch.run(dims, hp, seed, checked, **kw)
                emit({"cell": cell.name, "seed": seed, "kind": kind,
                      "numbers": compare.numbers(other, ref),
                      "losses": other.losses})
    if out:
        out.close()
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
