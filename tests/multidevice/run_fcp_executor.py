"""Multi-device FCP executor correctness check (run in a subprocess).

Builds a random packed varlen batch, runs distributed FCP attention on 8
host devices through the full pipeline (reshuffle -> matching ppermute
rounds -> restore), and compares against the dense single-device oracle
over the whole stream.  Also checks gradients.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       PYTHONPATH=src python tests/multidevice/run_fcp_executor.py \
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
from repro.core import executor                                 # noqa: E402
from repro.core import policies                                 # noqa: E402
from repro.kernels import ref                                   # noqa: E402
from _common import grid                                        # noqa: E402


def run_case(seqlens, n_workers, tokens_per_worker, block_size, mesh_shape,
             mesh_axes, hq, kh, d, mask, policy="fcp", n_pods=1, seed=0,
             check_grad=True, coalesce=1, return_out=False):
    rng = np.random.default_rng(seed)
    sched = make_schedule(seqlens, n_workers, tokens_per_worker, block_size,
                          n_q_heads=hq, n_kv_heads=kh, head_dim=d,
                          mask=mask, coalesce=coalesce)
    if policy == "ring":    # baselines run through the same executor
        a = policies.assign_ring(sched.batch, n_workers)
        sched = make_schedule(seqlens, n_workers, tokens_per_worker,
                              block_size, n_q_heads=hq, n_kv_heads=kh,
                              head_dim=d, mask=mask, assignment=a,
                              coalesce=coalesce)
    n_tok = sched.batch.n_tokens                 # per pod
    total = n_pods * n_tok
    q = jnp.asarray(rng.normal(size=(total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(total, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(total, kh, d)), jnp.float32)
    seg = jnp.asarray(sched.batch.seg_ids)
    pos = jnp.asarray(sched.batch.positions)

    # oracle: independent attention per pod stream
    o_ref = np.zeros((total, hq, d), np.float32)
    for p in range(n_pods):
        sl = slice(p * n_tok, (p + 1) * n_tok)
        o_p, _ = ref.reference_attention(
            q[sl].transpose(1, 0, 2), k[sl].transpose(1, 0, 2),
            v[sl].transpose(1, 0, 2), seg, pos, seg, pos, mask)
        o_ref[sl] = np.asarray(o_p.transpose(1, 0, 2))

    mesh = jax.make_mesh(mesh_shape, mesh_axes)
    tpw = tokens_per_worker
    F = total // tpw

    def shaped(x):
        return x.reshape(F, tpw, x.shape[-2], x.shape[-1])

    tables = executor.schedule_tables(sched)
    head_axis = "model" if "model" in mesh_axes else None

    def fcp(q, k, v):
        return executor.fcp_attention(
            q, k, v, tables, spec=sched.spec, mesh=mesh, cp_axis="data",
            head_axis=head_axis)

    o = jax.jit(fcp)(shaped(q), shaped(k), shaped(v))
    o = np.asarray(o).reshape(total, hq, d)
    err = np.abs(o - o_ref).max()
    assert err < 2e-4, f"forward mismatch: {err}"

    if check_grad:
        key = jnp.asarray(rng.normal(size=o_ref.shape), jnp.float32)

        def loss_fcp(q, k, v):
            o = fcp(shaped(q), shaped(k), shaped(v))
            return jnp.sum(o.reshape(total, hq, d) * key)

        def loss_ref(q, k, v):
            tot = 0.0
            for p in range(n_pods):
                sl = slice(p * n_tok, (p + 1) * n_tok)
                o, _ = ref.reference_attention(
                    q[sl].transpose(1, 0, 2), k[sl].transpose(1, 0, 2),
                    v[sl].transpose(1, 0, 2), seg, pos, seg, pos, mask)
                tot = tot + jnp.sum(o.transpose(1, 0, 2) * key[sl])
            return tot

        g_f = jax.jit(jax.grad(loss_fcp, argnums=(0, 1, 2)))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_f, g_r, "qkv"):
            gerr = np.abs(np.asarray(a) - np.asarray(b)).max()
            scale = max(1e-6, np.abs(np.asarray(b)).max())
            assert gerr / scale < 5e-4, f"d{name} mismatch: {gerr} ({scale})"
    if return_out:
        return err, o
    return err


# a long-tailed causal batch: 16 blocks of 256 on 8 workers, 11
# congestion-free rounds at C = 1, 3 at C = 4, 1 at C = 16
LONG_TAILED = dict(seqlens=[2048, 1024, 512, 300, 212], n_workers=8,
                   tokens_per_worker=512, block_size=256, mesh_shape=(8,),
                   mesh_axes=("data",), hq=4, kh=2, d=32, mask=True)


def check_full_grid(err4, o4):
    # C > 1 must also match the C = 1 schedule of the same batch (same
    # assignment, same pairs — only comm round structure changes)
    err1, o1 = run_case(**LONG_TAILED, seed=7, coalesce=1, return_out=True)
    print(f"coalesce=1: max fwd err {err1:.2e}  OK")
    err16, o16 = run_case(**LONG_TAILED, seed=7, check_grad=False,
                          coalesce=16, return_out=True)
    for C, errc, oc in ((4, err4, o4), (16, err16, o16)):
        dev = np.abs(oc - o1).max()
        assert dev < 1e-4, f"coalesce={C} output drifted from C=1: {dev}"
        print(f"coalesce={C}: max fwd err {errc:.2e}  "
              f"|o - o(C=1)| {dev:.2e}  OK")

    cases = [
        dict(seqlens=[512] * 16, n_workers=8, tokens_per_worker=1024,
             block_size=256, mesh_shape=(8,), mesh_axes=("data",),
             hq=4, kh=2, d=32, mask=True, seed=100),       # packed short
        dict(seqlens=[6000, 1500], n_workers=4, tokens_per_worker=2048,
             block_size=512, mesh_shape=(4, 2), mesh_axes=("data", "model"),
             hq=4, kh=2, d=32, mask=True, seed=102),       # CP x TP
        dict(seqlens=[3000, 1000], n_workers=4, tokens_per_worker=1024,
             block_size=256, mesh_shape=(2, 4), mesh_axes=("pod", "data"),
             hq=2, kh=2, d=16, mask=True, n_pods=2,
             seed=103),                                    # multi-pod DP
        dict(seqlens=[2048, 1024, 512], n_workers=8,
             tokens_per_worker=512, block_size=256, mesh_shape=(8,),
             mesh_axes=("data",), hq=2, kh=1, d=16, mask=False,
             seed=104),                                    # non-causal
        dict(LONG_TAILED, policy="ring", check_grad=False,
             seed=105),                                    # ring baseline
    ]
    for i, c in enumerate(cases):
        err = run_case(**c)
        print(f"case {i}: max fwd err {err:.2e}  OK")


def main():
    full = grid() == "full"
    # §4.2 coalescer: C = 4 windows the rounds; vs the oracle, with grads
    err4, o4 = run_case(**LONG_TAILED, seed=7, coalesce=4, return_out=True)
    print(f"coalesce=4: max fwd err {err4:.2e}  OK")
    if full:
        check_full_grid(err4, o4)
    print("ALL MULTIDEVICE EXECUTOR CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
