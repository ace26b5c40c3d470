"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Set-up builds the driver (weights from the seed, the loader, the
planner) and drives one step per composition of the traffic through the
same call the window makes, so every program the window uses is
compiled (or loaded from the compile cache) before it opens.  The first
three of those steps are the ones the reference follows.  The window
then trains whole passes over the compositions until ``seconds`` have
passed.  A traced run records the window's first ``trace_passes``
passes, where the traffic names that many, and all of it otherwise.
Once the window has closed and the device's peak memory is read, the
program's state is dropped and the reference runs the first three
steps again from the seed.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from . import compare, flops, reference, registry, trace_reduce

CHECK_STEPS = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: registry.Cell
    dims: object                # the sizes of ``cell.arch``
    peak: dict
    chips: int
    tokens_per_step: int
    setup_s: float
    window_s: float
    steps: list                 # seqlens of every step in the window
                                # (of every traced step, where traced)
    step_s: list                # host seconds of each step, to its sync
    compiles_in_window: int
    memory_peak_bytes: int | None
    trace: trace_reduce.TraceView | None = None


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    it is open (a context manager)."""

    def __enter__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)

    def _on(self, event: str, *_a, **_k) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def check_devices(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's devices are {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int | None:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def warm_up(drv, arch, dims, hp, seed: int):
    """One step per composition.  Returns the step records and the
    program's readings: the first three losses, the first gradient's
    norms (read after the first step) and the change of the weights
    (read after the third, against the seed's weights drawn again by
    ``arch`` where the program holds its own)."""
    import jax

    records, grads, change = [], None, None
    for i in range(max(len(drv.compositions), CHECK_STEPS)):
        records.append(drv.step())
        if i == 0:
            grads = reference.leaf_norms(drv.opt.m, 1.0 / (1.0 - hp.b1))
        if i == CHECK_STEPS - 1:
            where = jax.tree.map(lambda a: a.sharding, drv.params)
            change = reference.change_norms(
                drv.params, arch.maker(dims, out_shardings=where)(
                    reference.seed_key(seed)))
    prog = reference.Readings(
        losses=[r.loss for r in records[:CHECK_STEPS]], grad_norms=grads,
        update_norms=change)
    return records, prog


def window(drv, seconds: float, trace_dir: str | None = None,
           trace_passes: int = 0):
    """Whole passes over the compositions until ``seconds`` have passed.
    With ``trace_dir`` the profiler writes its trace of the first
    ``trace_passes`` passes there (of the whole window where 0); the
    ``bench.window`` span marks the traced part.  Returns the steps,
    their host seconds, the window's seconds, the programs obtained
    inside it and the number of steps traced."""
    import jax
    from jax.profiler import TraceAnnotation

    steps, step_s = [], []
    traced = None               # steps traced, once the span has closed

    def close_span():
        nonlocal traced
        span.__exit__(None, None, None)
        traced = len(steps)
        if trace_dir is not None:
            jax.profiler.stop_trace()

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    # made once the profiler runs: a span made before it records nothing
    span = TraceAnnotation(trace_reduce.WINDOW_SPAN)
    span.__enter__()
    with CompileCounter() as counter:
        t0 = t_prev = time.perf_counter()
        while t_prev - t0 < seconds:
            for _ in drv.compositions:
                steps.append(drv.step())
                t = time.perf_counter()
                step_s.append(t - t_prev)
                t_prev = t
            if (traced is None and trace_dir is not None
                    and len(steps) == trace_passes * len(drv.compositions)):
                close_span()
        window_s = t_prev - t0
        if traced is None:
            close_span()
    return steps, step_s, window_s, counter.n, traced


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_chip: bool = True,
             peak: dict | None = None,
             compile_cache: bool = True,
             bench_dir=registry.BENCH_DIR) -> dict:
    """One run.  The keyword arguments after ``t_start`` exist for the
    CPU tests: they skip the look for a chip, give a peak for a device
    the table does not know, and keep the compile cache off."""
    import jax

    device = check_devices(cell.chips, require_chip)
    peak = peak or flops.peaks(device["kind"])
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    dims = cell.arch.sizes(cell.config)
    hp = reference.Hyper.from_traffic(cell.traffic)
    drv = registry.driver(cell.traffic["driver"], bench_dir).Driver(
        cell, dims, seed, hp)
    first, prog = warm_up(drv, cell.arch, dims, hp, seed)
    checked = [(s.tokens, s.seqlens) for s in first[:CHECK_STEPS]]
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    steps, step_s, window_s, compiles, traced = window(
        drv, seconds, trace_dir, int(cell.traffic.get("trace_passes", 0)))
    view = None
    if trace:
        view = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = memory_peak(cell.chips)

    drv.free()
    del drv
    ref = cell.arch.run(dims, hp, seed, checked)
    ok, compared = compare.judge(compare.numbers(prog, ref), cell.limits)

    run = Run(cell=cell, dims=dims, peak=peak, chips=cell.chips,
              tokens_per_step=int(cell.traffic["workers"])
              * int(cell.traffic["tokens_per_worker"]),
              setup_s=setup_s, window_s=window_s,
              steps=[s.seqlens for s in steps[:traced]],
              step_s=step_s[:traced],
              compiles_in_window=compiles,
              memory_peak_bytes=mem, trace=view)
    metrics = {}
    for m in cell.metrics(trace):
        value = registry.metric(m["name"], bench_dir).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(1 for s in steps if not math.isfinite(s.loss))
    device = dict(device, memory_peak_bytes=mem)
    if trace:
        device.update(busy_s=trace_reduce.busy_s(view),
                      window_s=view.window_s)
    out = {"correct": bool(ok and failed == 0), "attempted": len(steps),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(view),
                            "idle_gaps": trace_reduce.idle_gaps(view)}
    out["compared"] = compared
    print(f"window: {len(steps)} steps in {window_s:.3f} s"
          f"{f' ({traced} traced)' if trace else ''}, "
          f"{compiles} program(s) obtained inside it; steps (ms) "
          f"{[round(t * 1e3, 1) for t in step_s]}", file=sys.stderr)
    print(f"losses {prog.losses} (reference {ref.losses})",
          file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']:.6e} (limit {c['limit']:.3e},"
              f" at {c['at']})", file=sys.stderr)
    sys.stderr.flush()
    return out


def to_json_safe(x):
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: to_json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [to_json_safe(v) for v in x]
    if isinstance(x, np.generic):
        return x.item()
    return x

