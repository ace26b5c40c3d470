"""Double-buffered (overlap) executor equivalence (run in a subprocess).

The software-pipelined round loop (``StaticSpec.overlap``;
docs/overlap.md) issues round r+1's sends BEFORE run r's compute,
gathering payloads from an immutable snapshot of the local KV slots and
landing arrivals in double-buffered (parity-allocated) receive slots.
The whole point is that this is a pure *scheduling* transform — the
bytes on the wire and the attention math are identical.  This suite
locks that down:

* overlap-on vs overlap-off under the f32 wire: forward outputs, loss
  and dq are BITWISE equal across coalesce 1/4/16 and causal / sliding-
  window masks, per-step and fused impls.  dk/dv are equal to <= 1e-6
  normalized but NOT bitwise: the backward scatter-add association
  trees differ (serial send-gathers read the kxt commit chain, so their
  cotangents interleave into the per-round chain; overlap send-gathers
  read the frozen ksrc/vsrc snapshot, so their cotangents sum through a
  single concat-VJP) and float addition is not associative.  The
  forward payloads themselves are bitwise identical — docs/overlap.md
  records the argument.
* the overlap executor still reproduces the dense single-device oracle
  to 1e-6 (transitively through the f32-wire check, asserted directly).
* the layer-pipelined reshuffle primitive (``fcp_reshuffle``): a
  stream -> schedule -> stream round trip of a per-token tensor (with
  an integer positions channel riding as f32) is BITWISE the identity,
  and running attention in ``layout="sched"`` between two explicit
  reshuffles is BITWISE equal to the ordinary ``layout="stream"`` call
  — the per-layer Q/K/V reshuffle and the group-boundary hidden-state
  move are the same plan shipping the same f32 payloads.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       PYTHONPATH=src python tests/multidevice/run_overlap_executor.py \
       [tier1|full]
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro import masks                                         # noqa: E402
from repro.core import executor                                 # noqa: E402
from _common import (build, exec_fn, grid, out_loss_grads,      # noqa: E402
                     ref_fn, rel_err)

ORACLE_TOL = 1e-6          # overlap + f32 wire vs dense oracle
DKDV_TOL = 1e-6            # dk/dv association-order drift, normalized


# --------------------------------------------------------------------------
# overlap on/off equivalence
# --------------------------------------------------------------------------

def check_overlap_equivalence(seqlens, mask, coalesce, impl="xla",
                              n_workers=8, tpw=512, bs=128, hq=4, kh=2,
                              d=32, seed=0):
    mesh = jax.make_mesh((n_workers,), ("data",))
    runs = {}
    for overlap in (False, True):
        sched, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq, kh,
                                    d, seed=seed, mask=mask,
                                    coalesce=coalesce, overlap=overlap)
        assert sched.spec.overlap == overlap
        runs[overlap] = out_loss_grads(
            exec_fn(sched, mesh, tpw, impl=impl), q, k, v, key)
        if not overlap:
            oerr = rel_err(runs[overlap][0], ref_fn(sched, mask)(q, k, v))
            assert oerr < ORACLE_TOL, f"vs oracle: {oerr:.2e}"

    o0, l0, (dq0, dk0, dv0) = runs[False]
    o1, l1, (dq1, dk1, dv1) = runs[True]
    assert np.array_equal(o0, o1), \
        f"{mask} C={coalesce} [{impl}]: forward not bitwise"
    assert np.array_equal(l0, l1), \
        f"{mask} C={coalesce} [{impl}]: loss not bitwise"
    assert np.array_equal(dq0, dq1), \
        f"{mask} C={coalesce} [{impl}]: dq not bitwise"
    dkerr, dverr = rel_err(dk1, dk0), rel_err(dv1, dv0)
    assert dkerr < DKDV_TOL, f"dk drift {dkerr:.2e}"
    assert dverr < DKDV_TOL, f"dv drift {dverr:.2e}"
    print(f"  {str(mask):12s} C={coalesce:2d} [{impl:9s}]  "
          f"out/loss/dq bitwise, dk {dkerr:.1e} dv {dverr:.1e}  OK")


# --------------------------------------------------------------------------
# layer-pipelined reshuffle primitive
# --------------------------------------------------------------------------

def check_reshuffle_roundtrip(seqlens, mask, n_workers=8, tpw=512,
                              bs=128, seed=4):
    sched, *_ = build(seqlens, n_workers, tpw, bs, 2, 1, 16, seed=seed,
                      mask=mask, coalesce=4)
    mesh = jax.make_mesh((n_workers,), ("data",))
    tables = executor.schedule_tables(sched)
    rng = np.random.default_rng(seed)
    C = 24
    x = jnp.asarray(rng.normal(size=(n_workers, tpw, C)), jnp.float32)
    pos = jnp.asarray(sched.batch.positions.reshape(n_workers, tpw),
                      jnp.int32)
    xp = jnp.concatenate([x, pos.astype(jnp.float32)[..., None]],
                         axis=-1)

    def trip(xp):
        y = executor.fcp_reshuffle(xp, tables, spec=sched.spec,
                                   mesh=mesh, cp_axis="data")
        return executor.fcp_reshuffle(y, tables, spec=sched.spec,
                                      mesh=mesh, cp_axis="data",
                                      reverse=True)

    back = np.asarray(jax.jit(trip)(xp))
    assert np.array_equal(back[..., :C], np.asarray(x)), \
        "hidden-state round trip not bitwise identity"
    assert np.array_equal(
        np.round(back[..., C]).astype(np.int32), np.asarray(pos)), \
        "positions channel did not survive the round trip"
    print(f"  {str(mask):12s} fcp_reshuffle round trip bitwise  OK")


def check_sched_layout_attention(seqlens, mask, n_workers=8, tpw=512,
                                 bs=128, hq=4, kh=2, d=32, seed=5):
    """reshuffle -> layout='sched' attention -> reverse reshuffle must
    be bitwise the ordinary layout='stream' call."""
    sched, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq, kh, d,
                                seed=seed, mask=mask, coalesce=4)
    mesh = jax.make_mesh((n_workers,), ("data",))
    tables = executor.schedule_tables(sched)
    spec = sched.spec

    def sh(x):
        return x.reshape(n_workers, tpw, x.shape[-2], x.shape[-1])

    def resh(x, reverse=False):
        F, T, h, dd = x.shape
        y = executor.fcp_reshuffle(x.reshape(F, T, h * dd), tables,
                                   spec=spec, mesh=mesh, cp_axis="data",
                                   reverse=reverse)
        return y.reshape(F, T, h, dd)

    def stream(q, k, v):
        return executor.fcp_attention(sh(q), sh(k), sh(v), tables,
                                      spec=spec, mesh=mesh,
                                      cp_axis="data", head_axis=None)

    def pipelined(q, k, v):
        qs, ks, vs = resh(sh(q)), resh(sh(k)), resh(sh(v))
        o = executor.fcp_attention(qs, ks, vs, tables, spec=spec,
                                   mesh=mesh, cp_axis="data",
                                   head_axis=None, layout="sched")
        return resh(o, reverse=True)

    o_s = np.asarray(jax.jit(stream)(q, k, v))
    o_p = np.asarray(jax.jit(pipelined)(q, k, v))
    assert np.array_equal(o_s, o_p), \
        "sched-layout attention not bitwise vs stream layout"
    print(f"  {str(mask):12s} layout='sched' == layout='stream' "
          f"bitwise  OK")


def check_full_grid(long_tailed):
    swa = masks.sliding_window(600)
    for coalesce in (1, 16):
        check_overlap_equivalence(long_tailed, masks.CAUSAL, coalesce,
                                  impl="xla", seed=coalesce)
    check_overlap_equivalence(long_tailed, swa, 4, impl="xla", seed=7)
    check_overlap_equivalence(long_tailed, masks.CAUSAL, 4,
                              impl="fused_xla", seed=8)

    print("layer-pipelined reshuffle:")
    check_reshuffle_roundtrip(long_tailed, swa)
    check_sched_layout_attention(long_tailed, masks.CAUSAL)
    check_sched_layout_attention(long_tailed, swa)


def main():
    full = grid() == "full"
    long_tailed = [1536, 1024, 512, 300, 212, 512]
    print("overlap on/off equivalence (outputs + loss + grads):")
    check_overlap_equivalence(long_tailed, masks.CAUSAL, 4, impl="xla",
                              bs=256, seed=4)
    check_reshuffle_roundtrip(long_tailed, masks.CAUSAL)
    if full:
        check_full_grid(long_tailed)
    print("ALL OVERLAP EXECUTOR CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
