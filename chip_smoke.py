"""Run the FCP trainer's main path once on a TPU and check what it computes.

One chip (the default), at ``stablelm_1_6b``'s published widths:

1. kernel parity: FCP attention (``executor.fcp_attention`` driving the
   fused Pallas kernel) against the float32 reference of
   ``kernels/ref.py``, outputs and gradients, on a seeded packed
   ``real_world`` batch;
2. trainer: ``repro.launch.train.main`` for 3 steps through the planner,
   the plan cache, the executor and the kernel, with the depth cut to
   ``ONE_CHIP_LAYERS``; the compiled train step must hold the Pallas
   kernel (``tpu_custom_call``).

``--four-chips`` runs only what exists across chips: the parity check
with FCP split over a 4-chip mesh, and ``train.main`` on a 4x1 mesh at
the full 24 layers, followed by the bytes each device holds.

The last line of standard output is one JSON object naming the device,
printed only when every check passed.  Without a TPU, or outside a
checkout of the repository, the script exits non-zero.

    python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys

ARCH = "stablelm_1_6b"
SEED = 0
TOKENS_PER_WORKER = 8192
BLOCK_SIZE = 1024
STEPS = 3
# Depth on one chip: the largest whose train step stays under ~13 GB in
# memory_analysis() (arguments + temporaries) on a described v5e at
# 8192 tokens.  1 layer: 12.96 GB, 2: 13.93 GB, 4: 16.36 GB.  The f32
# logits and their gradient over the 100352-token vocabulary make most
# of the ~7.9 GB of temporaries at any depth.
ONE_CHIP_LAYERS = 1
# The full 24 layers on four chips at 8192 tokens per worker need 19.95 GB
# per chip (the compiler refuses it); 2048 tokens per worker compile.
FOUR_CHIP_TOKENS_PER_WORKER = 2048
# Parity bound: max |fcp - ref| / max |ref|, per tensor.  Inputs are
# bf16 and the kernel accumulates in f32, so bf16 rounding (~4e-3) is
# the scale of honest error.
PARITY_BOUND = 1e-2
# Step 0 of a randomly initialised model sits near ln(vocab).
LOSS0_TOL = 1.0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(failures: list[str], ok: bool, msg: str) -> None:
    """Record a failed check; the script exits non-zero once every phase
    has reported (an exception in a phase still ends it at once)."""
    if not ok:
        print(f"chip_smoke: check failed: {msg}", file=sys.stderr,
              flush=True)
        failures.append(msg)


def attention_parity(n_workers: int, failures: list[str]) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core import executor, make_schedule
    from repro.data.distributions import sample_composition
    from repro.kernels import ref
    from repro.launch.mesh import make_mesh

    cfg = get_config(ARCH)
    h, kh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    total = n_workers * TOKENS_PER_WORKER
    seqlens = sample_composition("real_world", total, seed=SEED)
    sched = make_schedule(seqlens, n_workers, TOKENS_PER_WORKER, BLOCK_SIZE,
                          n_q_heads=h, n_kv_heads=kh, head_dim=d, mask=True)
    mesh = make_mesh((n_workers, 1), ("data", "model"))
    tables = executor.schedule_tables(sched)
    seg = jnp.asarray(sched.batch.seg_ids)
    pos = jnp.asarray(sched.batch.positions)
    keys = jax.random.split(jax.random.key(SEED), 4)
    q = jax.random.normal(keys[0], (total, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (total, kh, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (total, kh, d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (total, h, d), jnp.float32)
    print(f"parity: {n_workers} worker(s), {len(seqlens)} documents "
          f"(longest {max(seqlens)}), {total} tokens, heads {h}/{kh}, "
          f"head_dim {d}, block {BLOCK_SIZE}, {sched.spec.n_rounds} "
          f"round(s), {sched.spec.n_runs} run(s)", flush=True)

    def fcp(q, k, v):
        def frames(x):
            return x.reshape(n_workers, TOKENS_PER_WORKER, *x.shape[1:])
        o = executor.fcp_attention(
            frames(q), frames(k), frames(v), tables, spec=sched.spec,
            mesh=mesh, cfg=executor.ExecConfig(impl="fused"))
        return o.reshape(total, h, d)

    # float32 dense oracle, one head and ``rows`` queries at a time so
    # the score matrix stays small; rematerialised under grad
    rows = min(4096, total)
    one = jax.checkpoint(functools.partial(ref.reference_attention,
                                           mask=True))

    def reference(q, k, v, seg, pos):
        k, v = (jnp.repeat(x, h // kh, axis=1) for x in (k, v))

        def head(qkv):
            qh, kh_, vh = (x.astype(jnp.float32)[None] for x in qkv)
            return jnp.concatenate(
                [one(qh[:, s:s + rows], kh_, vh, seg[s:s + rows],
                     pos[s:s + rows], seg, pos)[0]
                 for s in range(0, total, rows)], axis=1)[0]

        o = jax.lax.map(head, tuple(x.transpose(1, 0, 2)
                                    for x in (q, k, v)))
        return o.transpose(1, 0, 2)

    # w (and the reference's seg/pos) are arguments, not closure
    # constants baked into the executable
    def out_and_grads(fn):
        def loss(q, k, v, w, *meta):
            o = fn(q, k, v, *meta)
            return jnp.sum(o * w), o
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))

    (gq, gk, gv), o = out_and_grads(fcp)(q, k, v, w)
    with jax.default_matmul_precision("highest"):
        (rq, rk, rv), o_ref = out_and_grads(reference)(q, k, v, w, seg,
                                                       pos)
    for name, a, b in (("o", o, o_ref), ("dq", gq, rq), ("dk", gk, rk),
                       ("dv", gv, rv)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        print(f"parity {name}: max|fcp-ref|/max|ref| = {err:.3e} "
              f"(bound {PARITY_BOUND:.0e})", flush=True)
        check(failures, err <= PARITY_BOUND,
              f"{n_workers}-worker {name} parity {err:.3e} exceeds "
              f"{PARITY_BOUND:.0e}")


def run_trainer(mesh: str, tokens_per_worker: int, n_layers: int,
                failures: list[str]):
    from repro.configs.base import get_config
    from repro.launch import train

    vocab = get_config(ARCH).vocab_size
    argv = ["--arch", ARCH, "--mesh", mesh, "--attn-impl", "fused",
            "--dist", "real_world",
            "--tokens-per-worker", str(tokens_per_worker),
            "--block-size", str(BLOCK_SIZE), "--steps", str(STEPS),
            "--override", f"n_layers={n_layers}"]
    print("trainer: repro.launch.train.main " + " ".join(argv), flush=True)
    res = train.main(argv)
    print(f"trainer: losses {res.losses} (ln vocab {math.log(vocab):.4f}),"
          f" {res.plan_misses} FCP plan(s) built", flush=True)
    finite = (len(res.losses) == STEPS
              and all(map(math.isfinite, res.losses)))
    check(failures, finite,
          f"expected {STEPS} finite losses, got {res.losses}")
    if finite:
        check(failures, abs(res.losses[0] - math.log(vocab)) <= LOSS0_TOL,
              f"step-0 loss {res.losses[0]:.4f} is not near "
              f"ln({vocab}) = {math.log(vocab):.4f}")
    check(failures, res.plan_misses >= 1,
          "the trainer built no FCP plan: the executor did not run")
    return res


def print_memory() -> None:
    """Bytes each device holds now (the trained state is alive) and at
    its peak."""
    import jax

    for dev in jax.devices():
        ms = dev.memory_stats() or {}
        print(f"device {dev.id}: bytes_in_use {ms.get('bytes_in_use')} "
              f"peak_bytes_in_use {ms.get('peak_bytes_in_use')}", flush=True)


def one_chip(failures: list[str]) -> None:
    from repro.configs.base import get_config

    print(f"cut from published size: n_layers={ONE_CHIP_LAYERS} "
          f"(published {get_config(ARCH).n_layers})", flush=True)
    attention_parity(1, failures)
    res = run_trainer("1x1", TOKENS_PER_WORKER, ONE_CHIP_LAYERS, failures)
    hlo = res.step_fn.lower(res.params, res.opt, res.residual,
                            res.batch).compile().as_text()
    n = hlo.count("tpu_custom_call")
    print(f"trainer: compiled train step holds {n} tpu_custom_call(s)",
          flush=True)
    check(failures, n > 0, "the compiled train step holds no Pallas kernel")
    print_memory()


def four_chips(failures: list[str]) -> None:
    import jax

    from repro.configs.base import get_config

    if len(jax.devices()) != 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    attention_parity(4, failures)
    res = run_trainer("4x1", FOUR_CHIP_TOKENS_PER_WORKER,
                      get_config(ARCH).n_layers, failures)
    print_memory()
    del res


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-chip FCP path and its parity check")
    args = p.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r}")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.device_kind} x {len(jax.devices())}, compile "
          f"cache {enable_compile_cache()}", flush=True)

    failures: list[str] = []
    if args.four_chips:
        four_chips(failures)
    else:
        one_chip(failures)
    if failures:
        fail(f"{len(failures)} check(s) failed: " + "; ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
