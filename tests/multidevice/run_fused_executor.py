"""Fused-vs-per-step executor equivalence (run in a subprocess).

The fused executor (one ``fused_run_attention`` launch per run) must
reproduce the per-step executor (one ``block_attention`` + merge per
schedule step) to float32 round-off — outputs AND gradients — across
random schedules and coalescer degrees, and its traced launch count must
drop from ``n_steps`` to ``n_runs <= n_rounds + 1``.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       PYTHONPATH=src python tests/multidevice/run_fused_executor.py \
       [tier1|full]
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro.kernels import ops                                   # noqa: E402
from _common import build, exec_fn, grid, rel_err               # noqa: E402

TOL = 4e-7          # fused-vs-per-step, normalized


def count_launches(sched, mesh, tpw, impl, q, k, v):
    """Trace the executor and count attention-op calls per worker."""
    return ops.count_attention_launches(
        exec_fn(sched, mesh, tpw, impl=impl), q, k, v)


def check_case(seqlens, n_workers, tpw, bs, hq, kh, d, coalesce, seed,
               check_grad):
    sched, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq, kh, d,
                                seed=seed, mask=True, coalesce=coalesce)
    spec = sched.spec
    mesh = jax.make_mesh((n_workers,), ("data",))

    per_step = exec_fn(sched, mesh, tpw, impl="xla")
    fused = exec_fn(sched, mesh, tpw, impl="fused_xla")
    err = rel_err(jax.jit(fused)(q, k, v), jax.jit(per_step)(q, k, v))
    assert err < TOL, f"C={coalesce}: fused output drifted {err:.2e}"

    gerrs = []
    if check_grad:
        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * key)

        g_s = jax.jit(jax.grad(loss(per_step), argnums=(0, 1, 2)))(q, k, v)
        g_f = jax.jit(jax.grad(loss(fused), argnums=(0, 1, 2)))(q, k, v)
        for a, b, name in zip(g_f, g_s, "qkv"):
            gerr = rel_err(a, b)
            assert gerr < TOL, f"C={coalesce} d{name}: {gerr:.2e}"
            gerrs.append(gerr)

    # launch accounting: fused path must collapse n_steps launches into
    # <= n_rounds + 1 runs
    c_step = count_launches(sched, mesh, tpw, "xla", q, k, v)
    c_fused = count_launches(sched, mesh, tpw, "fused_xla", q, k, v)
    assert c_step["step"] == spec.n_steps, c_step
    assert c_fused["step"] == 0, c_fused
    assert c_fused["fused"] <= spec.n_rounds + 1, \
        (c_fused, spec.n_rounds)
    assert c_fused["fused"] <= spec.n_runs    # empty runs are skipped
    print(f"  C={coalesce}: |o_f - o_s| {err:.2e}"
          + (f"  grad {max(gerrs):.2e}" if gerrs else "")
          + f"  launches {c_step['step']} -> {c_fused['fused']}"
          f" (rounds {spec.n_rounds})")
    return c_step["step"], c_fused["fused"]


def lognormal_lens(rng, n_workers, tpw):
    """Random long-tailed document lengths filling the batch."""
    lens = []
    budget = n_workers * tpw
    while budget > 256:
        L = int(min(np.clip(rng.lognormal(6.5, 1.2), 128, 3072), budget))
        lens.append(L)
        budget -= L
    if budget:
        lens.append(budget)
    return lens


def check_fused_pallas():
    """The fused Pallas kernel end to end (interpret mode), small case."""
    sched, q, k, v, _ = build([512, 256, 128, 128], 4, 256, 128, 2, 1, 16,
                              seed=7, mask=True, coalesce=4)
    mesh = jax.make_mesh((4,), ("data",))
    o_s = jax.jit(exec_fn(sched, mesh, 256, impl="xla"))(q, k, v)
    o_p = jax.jit(exec_fn(sched, mesh, 256, impl="fused", interpret=True,
                          block_q=128, block_k=128))(q, k, v)
    err = rel_err(o_p, o_s)
    assert err < 2e-6, f"fused-pallas executor drifted: {err:.2e}"
    print(f"fused pallas (interpret) end-to-end: |o - o_s| {err:.2e}")


def main():
    # random long-tailed schedules (8 workers): tier1 runs case 0 at
    # C = 4 with grads, full the whole acceptance grid
    full = grid() == "full"
    rng = np.random.default_rng(0)
    base = dict(n_workers=8, tpw=512, bs=256, hq=4, kh=2, d=32)
    for case in range(2 if full else 1):
        lens = lognormal_lens(rng, base["n_workers"], base["tpw"])
        print(f"case {case}: seqlens={lens}")
        for C in (1, 4, 16) if full else (4,):
            check_case(lens, **base, coalesce=C, seed=100 + case,
                       check_grad=(case == 0))
    check_fused_pallas()
    print("ALL FUSED EXECUTOR CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
