"""Helpers shared by the multi-device runners in this directory.

A runner sets ``XLA_FLAGS`` before it imports this module, which
imports jax.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor, make_schedule
from repro.kernels import ref

GRIDS = ("tier1", "full")


def grid() -> str:
    """The case grid named by ``argv[1]``: ``tier1`` runs the one case
    that reaches most of the runner's mechanism (the tier-1 suite),
    ``full`` every case (the default, and the ``slow`` suite)."""
    name = sys.argv[1] if len(sys.argv) > 1 else "full"
    if name not in GRIDS:
        raise SystemExit(f"usage: {sys.argv[0]} [{'|'.join(GRIDS)}]")
    return name


def rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(1.0, np.abs(b).max())


def build(seqlens, n_workers, tpw, bs, hq, kh, d, seed=0, **sched_kw):
    """Schedule of one packed batch (``sched_kw`` to ``make_schedule``)
    and random ``q, k, v`` streams plus a loss cotangent ``key``."""
    sched = make_schedule(seqlens, n_workers, tpw, bs, n_q_heads=hq,
                          n_kv_heads=kh, head_dim=d, **sched_kw)
    rng = np.random.default_rng(seed)
    total = sched.batch.n_tokens

    def mk(h):
        return jnp.asarray(rng.normal(size=(total, h, d)), jnp.float32)
    return sched, mk(hq), mk(kh), mk(kh), mk(hq)


def exec_fn(sched, mesh, tpw, **cfg):
    """FCP attention of ``[total, heads, d]`` streams over ``mesh``'s
    ``data`` axis; ``cfg`` goes to ``executor.ExecConfig``."""
    tables = executor.schedule_tables(sched)
    config = executor.ExecConfig(**cfg)

    def fcp(q, k, v):
        total = q.shape[0]
        F = total // tpw

        def sh(x):
            return x.reshape(F, tpw, x.shape[-2], x.shape[-1])

        o = executor.fcp_attention(sh(q), sh(k), sh(v), tables,
                                   spec=sched.spec, mesh=mesh,
                                   cp_axis="data", head_axis=None,
                                   cfg=config)
        return o.reshape(total, q.shape[-2], q.shape[-1])
    return fcp


def ref_fn(sched, mask):
    """Dense single-device oracle over the batch's whole stream."""
    seg = jnp.asarray(sched.batch.seg_ids)
    pos = jnp.asarray(sched.batch.positions)

    def dense(q, k, v):
        o, _ = ref.reference_attention(
            q.transpose(1, 0, 2), k.transpose(1, 0, 2),
            v.transpose(1, 0, 2), seg, pos, seg, pos, mask)
        return o.transpose(1, 0, 2)
    return dense


def out_loss_grads(fn, q, k, v, key):
    """Jitted output, loss ``sum(fn(q, k, v) * key)`` and its q/k/v
    gradients, all on the host."""
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * key)

    o = np.asarray(jax.jit(fn)(q, k, v), np.float64)
    ls, g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return o, np.asarray(ls), [np.asarray(x) for x in g]
