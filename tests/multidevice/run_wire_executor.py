"""Quantized-wire executor equivalence (run in a subprocess).

The wire-format subsystem (runtime/wire.py) quantizes every FCP
ppermute payload — reshuffle Q/K/V, coalesced-round KV stacks, restore
of O — while kernels and merge math stay exact.  This suite locks down:

* ``wire.ship`` with the f32 format is BIT-EXACT with a raw
  ``lax.ppermute`` (forward and backward) — the quantized formats are
  custom-vjp wrapped, and the passthrough must not perturb anything;
* ``--comm-dtype bf16`` / ``int8`` executor outputs AND grads match the
  f32 wire within the documented tolerances (bf16 <= 1e-2, int8 <= 3e-2
  normalized) on causal, sliding-window and mixed layer-group
  schedules, across per-step and fused impls;
* the f32 wire still matches the dense single-device oracle to 1e-6;
* the ``attn_out_bf16`` restore-cast path (``ExecConfig.out_dtype``)
  matches the f32 restore within bf16 tolerance, outputs + grads
  (previously had zero direct coverage).

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       PYTHONPATH=src python tests/multidevice/run_wire_executor.py \
       [tier1|full]
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402
from jax import shard_map                                       # noqa: E402
from jax.sharding import PartitionSpec as P                     # noqa: E402

from repro import masks                                         # noqa: E402
from repro.runtime import wire                                  # noqa: E402
from _common import (build, exec_fn, grid, out_loss_grads,      # noqa: E402
                     ref_fn, rel_err)

ORACLE_TOL = 1e-6          # f32 wire vs dense oracle, normalized
WIRE_TOL = {"bf16": 1e-2, "int8": 3e-2}     # quantized vs f32 wire
OUT_BF16_TOL = 1e-2        # restore-cast path vs f32 restore


# --------------------------------------------------------------------------
# ship(f32) must be bit-exact with raw ppermute, fwd AND bwd
# --------------------------------------------------------------------------

def check_ship_f32_bit_exact(n_workers=8):
    mesh = jax.make_mesh((n_workers,), ("data",))
    perm = tuple((i, (i + 3) % n_workers) for i in range(n_workers - 2))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(n_workers, 3, 2, 8, 4)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def apply(fn):
        body = shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                         out_specs=P("data"), check_vma=False)
        out = jax.jit(body)(x)
        _, vjp = jax.vjp(body, x)
        return np.asarray(out), np.asarray(vjp(ct)[0])

    o_ship, g_ship = apply(
        lambda x: wire.ship(x[0], perm, "data", wire.WIRE_F32,
                            (-2, -1))[None])
    o_raw, g_raw = apply(
        lambda x: jax.lax.ppermute(x[0], "data", perm)[None])
    assert np.array_equal(o_ship, o_raw), "ship(f32) fwd not bit-exact"
    assert np.array_equal(g_ship, g_raw), "ship(f32) bwd not bit-exact"
    print("  ship(f32) == lax.ppermute bit-exact (fwd + bwd)  OK")


# --------------------------------------------------------------------------
# executor equivalence across wire formats
# --------------------------------------------------------------------------

def check_wire_formats(seqlens, mask, impl="xla", n_workers=8, tpw=512,
                       bs=128, hq=4, kh=2, d=32, seed=0):
    mesh = jax.make_mesh((n_workers,), ("data",))
    runs = {}
    for fmt in ("f32", "bf16", "int8"):
        sched, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq, kh,
                                    d, seed=seed, mask=mask, coalesce=4,
                                    wire=fmt)
        assert str(sched.spec.wire) == fmt
        runs[fmt] = out_loss_grads(exec_fn(sched, mesh, tpw, impl=impl),
                                   q, k, v, key)
        if fmt == "f32":
            # the exact wire still reproduces the dense oracle
            o_ref = ref_fn(sched, mask)(q, k, v)
            err = rel_err(runs[fmt][0], o_ref)
            assert err < ORACLE_TOL, f"f32 wire vs oracle: {err:.2e}"

    o32, _, g32 = runs["f32"]
    for fmt in ("bf16", "int8"):
        o, _, g = runs[fmt]
        err = rel_err(o, o32)
        gerr = max(rel_err(a, b) for a, b in zip(g, g32))
        tol = WIRE_TOL[fmt]
        assert err < tol, f"{mask} {fmt} [{impl}] fwd: {err:.2e}"
        assert gerr < tol, f"{mask} {fmt} [{impl}] grad: {gerr:.2e}"
        print(f"  {str(mask):12s} [{impl:9s}] {fmt:5s} vs f32:  "
              f"fwd {err:.2e}  grad {gerr:.2e}  (tol {tol:.0e})  OK")


def check_mixed_layer_groups(seqlens, mask_a, mask_b, n_workers=8,
                             tpw=512, bs=128, hq=4, kh=2, d=32, seed=3):
    """Two-layer chain, one schedule per mask (the per-layer-group train
    path), the whole chain re-run per wire format."""
    mesh = jax.make_mesh((n_workers,), ("data",))
    kh_take = kh

    def chain_fn(fmt):
        sched_a, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq,
                                      kh, d, seed=seed, mask=mask_a,
                                      coalesce=4, wire=fmt)
        sched_b, *_ = build(seqlens, n_workers, tpw, bs, hq, kh, d,
                            seed=seed, mask=mask_b, coalesce=4, wire=fmt)
        fcp_a = exec_fn(sched_a, mesh, tpw)
        fcp_b = exec_fn(sched_b, mesh, tpw)

        def f(q, k, v):
            h = fcp_a(q, k, v)
            q2 = h * 0.5 + q
            k2 = h[:, :kh_take] * 0.25 + k
            v2 = h[:, :kh_take] * 0.125 + v
            return fcp_b(q2, k2, v2)
        return f, q, k, v, key

    runs = {}
    for fmt in ("f32", "bf16", "int8"):
        f, q, k, v, key = chain_fn(fmt)
        runs[fmt] = out_loss_grads(f, q, k, v, key)
    o32, _, g32 = runs["f32"]
    for fmt in ("bf16", "int8"):
        o, _, g = runs[fmt]
        err = rel_err(o, o32)
        gerr = max(rel_err(a, b) for a, b in zip(g, g32))
        # two quantized hops in sequence: errors compound ~2x
        tol = 2 * WIRE_TOL[fmt]
        assert err < tol, f"mixed {fmt} fwd: {err:.2e}"
        assert gerr < tol, f"mixed {fmt} grad: {gerr:.2e}"
        print(f"  mixed {str(mask_a)}+{str(mask_b)} {fmt:5s} vs f32:  "
              f"fwd {err:.2e}  grad {gerr:.2e}  OK")


# --------------------------------------------------------------------------
# attn_out_bf16 restore-cast parity (ExecConfig.out_dtype)
# --------------------------------------------------------------------------

def check_out_bf16_parity(seqlens, n_workers=8, tpw=512, bs=128, hq=4,
                          kh=2, d=32, seed=9):
    sched, q, k, v, key = build(seqlens, n_workers, tpw, bs, hq, kh, d,
                                seed=seed, mask=masks.CAUSAL, coalesce=4,
                                wire="f32")
    mesh = jax.make_mesh((n_workers,), ("data",))
    o32, _, g32 = out_loss_grads(exec_fn(sched, mesh, tpw), q, k, v, key)
    obf, _, gbf = out_loss_grads(
        exec_fn(sched, mesh, tpw, out_dtype="bfloat16"), q, k, v, key)
    err = rel_err(obf, o32)
    gerr = max(rel_err(a, b) for a, b in zip(gbf, g32))
    assert err < OUT_BF16_TOL, f"out_dtype=bf16 fwd: {err:.2e}"
    assert gerr < OUT_BF16_TOL, f"out_dtype=bf16 grad: {gerr:.2e}"
    assert err > 0.0, "restore cast had no effect — dead knob?"
    print(f"  attn_out_bf16 restore-cast vs f32:  fwd {err:.2e}  "
          f"grad {gerr:.2e}  (tol {OUT_BF16_TOL:.0e})  OK")


def check_full_grid(long_tailed):
    check_wire_formats(long_tailed, masks.sliding_window(600),
                       impl="xla", seed=2)
    check_wire_formats(long_tailed, masks.CAUSAL, impl="fused_xla",
                       seed=1)

    print("mixed per-layer-group chains per wire format:")
    check_mixed_layer_groups(long_tailed, masks.sliding_window(600),
                             masks.CAUSAL)

    print("restore-cast path:")
    check_out_bf16_parity(long_tailed)


def main():
    full = grid() == "full"
    long_tailed = [1536, 1024, 512, 300, 212, 512]
    print("ship primitive:")
    check_ship_f32_bit_exact()

    print("executor wire-format equivalence (outputs + grads):")
    check_wire_formats(long_tailed, masks.CAUSAL, impl="xla", bs=256,
                       seed=1)
    if full:
        check_full_grid(long_tailed)
    print("ALL WIRE EXECUTOR CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
