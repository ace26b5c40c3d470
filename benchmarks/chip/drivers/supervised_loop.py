"""Context-parallel FCP training over several workers, driven as
``repro.launch.train.main --supervised`` drives it for ``n_cp > 1``: a
``Supervisor`` over a ``(workers, 1)`` mesh, then its ``_run_steps``,
here one step per :meth:`Driver.step`, with checkpointing (no
checkpoint directory) and fault injection (no failure, skew or rejoin)
off.  Each step is the supervisor's healthy path: the loader, one
cache-backed plan per mask group, the batch viewed on the fleet, the
compiled step (one per plan key, FSDP shardings), ``timed_call`` (the
dispatch, then a wait on every output), and the health monitor.

What the benchmark supplies instead of ``main``: as in
:mod:`inline_loop`, the weights (``cell.arch.maker`` from the seed, put
in the supervisor's shardings in place of those it drew itself), the
loader's seed (the run's) and its bounded set of compositions (the
traffic file's, in the order the seed gives them).  The supervisor's
own weights and moments are dropped before the seeded ones are drawn,
so the chips never hold two states.  ``_run_steps`` ends at the step
it runs, so it would not plan the next batch ahead; the driver calls
``_prefetch`` where the loop does for every step but its last (after
the step's lookup, before its dispatch), with the same speeds.

Host spans: ``bench.step`` around each ``_run_steps`` call and, inside
it, ``bench.loader`` (the loader), ``bench.plan`` (the supervisor's
plan lookup), ``bench.build`` (the compiled step's lookup, and its
build where the plan is new, then the plan-ahead request for the next
batch) and ``bench.dispatch`` (the call of the compiled step).  The wait for the step, the batch's arrays and the
health monitor read as ``bench.step``.
"""

from __future__ import annotations

import functools
import pathlib

from jax.profiler import TraceAnnotation

from chipbench import registry, traffic_gen

dense = registry.driver("inline_loop",
                        pathlib.Path(__file__).resolve().parents[1])


def _spanned(name: str, fn):
    @functools.wraps(fn)
    def call(*args, **kw):
        with TraceAnnotation(name):
            return fn(*args, **kw)
    return call


class Driver:
    def __init__(self, cell, dims, seed: int, hp):
        from repro.launch import train

        tr = cell.traffic
        n_cp, tpw = int(tr["workers"]), int(tr["tokens_per_worker"])
        cfg, pcfg, tcfg = dense.program_configs(cell, dims, seed, hp)
        self.compositions = traffic_gen.ordered(tr, seed)
        sup = train.Supervisor(cfg, pcfg, tcfg, n_workers=n_cp,
                               tokens_per_worker=tpw, dist="uniform",
                               uniform_len=n_cp * tpw, verbose=False)
        sup.loader.compositions = [list(c) for c in self.compositions]
        sup.params = sup.opt = None
        sup.params, sup.opt = dense.seeded_state(
            cell, dims, seed, sup._mesh(sup.pods, sup.n))
        self.sup, self.n_steps, self.batch = sup, 0, None
        self.speeds = None

        next_batch = sup.loader.next

        def loader_next():
            with TraceAnnotation("bench.loader"):
                self.batch = next_batch()
            return self.batch

        plan, step_fn = sup._plan, sup._step_fn

        def planned(seqlens, pods, n, speeds):
            self.speeds = speeds
            with TraceAnnotation("bench.plan"):
                return plan(seqlens, pods, n, speeds)

        def built(step, pods, n, *args):
            with TraceAnnotation("bench.build"):
                fn = step_fn(step, pods, n, *args)
                sup._prefetch(sup.loader.peek_seqlens(), pods, n,
                              self.speeds)
            return _spanned("bench.dispatch", fn)

        sup.loader.next = loader_next
        sup._plan = planned
        sup._step_fn = built

    @property
    def params(self):
        return self.sup.params

    @property
    def opt(self):
        return self.sup.opt

    def step(self):
        with TraceAnnotation("bench.step"):
            self.sup._run_steps(self.n_steps, self.n_steps + 1, None, None)
        self.n_steps += 1
        return dense.Step(tokens=self.batch.tokens,
                          seqlens=list(self.batch.seqlens),
                          loss=self.sup.history[-1].loss)

    def free(self) -> None:
        """Drop the trained state on every chip and the compiled steps,
        and stop the plan-ahead thread, so the devices are empty for the
        reference."""
        self.sup.planner.shutdown()
        self.sup._step_cache.clear()
        self.sup.params = self.sup.opt = self.sup.residual = None
        self.sup = self.batch = None
