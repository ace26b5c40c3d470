"""Masked multidevice FCP executor equivalence (run in a subprocess).

Sliding-window / chunked / full schedules — and a *mixed per-layer-group*
two-layer chain (one schedule per distinct MaskSpec, attention routed by
layer) — must reproduce the dense single-device oracle over the whole
stream: outputs AND gradients to <= 1e-6 (normalized).  Also asserts the
tentpole pruning property end-to-end: the sliding-window schedule ships
strictly fewer comm edges than the causal schedule of the same batch.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       PYTHONPATH=src python tests/multidevice/run_masked_executor.py \
       [tier1|full]
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402

from repro import masks                                         # noqa: E402
from repro.core import make_schedule                            # noqa: E402
from _common import (build, exec_fn, grid, out_loss_grads,      # noqa: E402
                     ref_fn, rel_err)

TOL = 1e-6          # executor vs dense oracle, normalized


def ref_grads(dense, q, k, v, key):
    """Un-jitted q/k/v gradients of the oracle's ``sum(o * key)``."""
    return jax.grad(lambda q, k, v: jnp.sum(dense(q, k, v) * key),
                    argnums=(0, 1, 2))(q, k, v)


def check_single_mask(seqlens, mask, n_workers=8, tpw=1024, bs=256, hq=4,
                      kh=2, d=32, impl="xla", interpret=False, seed=0):
    sched, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq, kh, d,
                                seed=seed, mask=mask, coalesce=4)
    mesh = jax.make_mesh((n_workers,), ("data",))
    fcp = exec_fn(sched, mesh, tpw, impl=impl, interpret=interpret,
                  block_q=128, block_k=128)
    dense = ref_fn(sched, mask)

    o, _, g_f = out_loss_grads(fcp, q, k, v, key)
    err = rel_err(o, dense(q, k, v))
    assert err < TOL, f"{mask} fwd: {err:.2e}"
    g_r = ref_grads(dense, q, k, v, key)
    gerr = max(rel_err(a, b) for a, b in zip(g_f, g_r))
    assert gerr < TOL, f"{mask} grad: {gerr:.2e}"
    print(f"  {str(mask):14s} [{impl}]  comm edges {len(sched.comm_edges):3d}"
          f"  fwd {err:.2e}  grad {gerr:.2e}  OK")
    return sched


def check_mixed_layer_groups(seqlens, mask_a, mask_b, n_workers=8,
                             tpw=1024, bs=256, hq=4, kh=2, d=32, seed=3):
    """Two-layer chain routed through per-mask schedules (the train
    path's per-layer-group structure): layer 1 under ``mask_a``, layer 2
    under ``mask_b``, gradients flowing through both executors."""
    sched_a, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq, kh, d,
                                  seed=seed, mask=mask_a, coalesce=4)
    sched_b = make_schedule(seqlens, n_workers, tpw, bs, n_q_heads=hq,
                            n_kv_heads=kh, head_dim=d, mask=mask_b,
                            coalesce=4)
    assert sched_a.spec != sched_b.spec or mask_a == mask_b
    mesh = jax.make_mesh((n_workers,), ("data",))
    fcp_a = exec_fn(sched_a, mesh, tpw, block_q=128, block_k=128)
    fcp_b = exec_fn(sched_b, mesh, tpw, block_q=128, block_k=128)
    dense_a = ref_fn(sched_a, mask_a)
    dense_b = ref_fn(sched_b, mask_b)
    kh_take = k.shape[-2]

    def chain(layer1, layer2):
        def f(q, k, v):
            h = layer1(q, k, v)                      # [total, hq, d]
            # cheap deterministic "projection" between the layers so the
            # second layer's q/k/v depend on the first layer's output
            q2 = h * 0.5 + q
            k2 = h[:, :kh_take] * 0.25 + k
            v2 = h[:, :kh_take] * 0.125 + v
            return layer2(q2, k2, v2)
        return f

    o, _, g_f = out_loss_grads(chain(fcp_a, fcp_b), q, k, v, key)
    err = rel_err(o, chain(dense_a, dense_b)(q, k, v))
    assert err < TOL, f"mixed fwd: {err:.2e}"
    g_r = ref_grads(chain(dense_a, dense_b), q, k, v, key)
    gerr = max(rel_err(a, b) for a, b in zip(g_f, g_r))
    assert gerr < TOL, f"mixed grad: {gerr:.2e}"
    print(f"  mixed {str(mask_a)} + {str(mask_b)}:  fwd {err:.2e}  "
          f"grad {gerr:.2e}  OK")


def check_full_grid():
    long_tailed = [4096, 2048, 1024, 512, 300, 200]
    check_single_mask(long_tailed, masks.chunked(1024), seed=12)
    check_single_mask(long_tailed, masks.FULL, seed=13)
    check_single_mask([8192], masks.sliding_window(512), seed=14)
    # fused executor impl under a window mask
    check_single_mask(long_tailed, masks.sliding_window(1000),
                      impl="fused_xla", seed=15)

    print("mixed per-layer-group schedules (two-layer chain):")
    check_mixed_layer_groups(long_tailed, masks.sliding_window(1000),
                             masks.CAUSAL)
    check_mixed_layer_groups(long_tailed, masks.chunked(2048),
                             masks.sliding_window(512))


def main():
    full = grid() == "full"
    print("single-mask schedules vs dense oracle (fwd + grad):")
    # W=1000: not a multiple of the 256 block — window cuts mid-block
    small = [2048, 1024, 512, 300, 212]
    s_swa = check_single_mask(small, masks.sliding_window(1000), tpw=512,
                              seed=11)
    s_causal = check_single_mask(small, masks.CAUSAL, tpw=512, seed=11)
    # the pruning property, end-to-end on identical batches
    assert len(s_swa.comm_edges) < len(s_causal.comm_edges), \
        (len(s_swa.comm_edges), len(s_causal.comm_edges))
    print(f"  swa ships {len(s_swa.comm_edges)} comm edges < causal "
          f"{len(s_causal.comm_edges)}  OK")
    if full:
        check_full_grid()
    print("ALL MASKED EXECUTOR CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
