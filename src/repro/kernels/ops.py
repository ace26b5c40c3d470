"""Jit'd public attention ops with implementation dispatch.

``block_attention`` is the primitive the FCP executor (and the dense
models) build on: normalized attention + lse over one (q, kv) range with
segment/position masking.

* ``impl="pallas"`` — the TPU kernel (``flash_attention.py``) behind a
  ``custom_vjp`` (Pallas forward + backward kernels).  Validated in
  interpret mode on CPU; the real target is TPU.
* ``impl="xla"``    — chunked pure-jnp flash (``ref.py``), plain autodiff.
  Portable path used on CPU and for 512-device dry-run lowering.
* ``impl="ref"``    — dense oracle (tests only).

``fused_run_attention`` is the run-granular primitive of the fused
executor: one call consumes a whole run of schedule steps (a table of
(q slot, extended-kv slot) pairs) against the executor's resident
buffers and folds the results into the per-slot flash accumulators.

* ``impl="pallas"`` — the schedule-table-driven fused kernels behind a
  ``custom_vjp`` whose backward exploits that the gradient of a merge
  chain collapses onto the run-final (o, lse) (see ``_fused_pl_bwd``).
* ``impl="xla"``    — vmap-batched attention over the run's steps plus a
  single scatter flash-merge; plain autodiff.  Exercises the identical
  run grouping on CPU.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..masks import CAUSAL, MaskSpec, coerce_mask
from . import flash_attention as fa
from . import ref


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    mask: MaskSpec = CAUSAL
    scale: float | None = None
    block_q: int = fa.DEFAULT_BLOCK_Q
    block_k: int = fa.DEFAULT_BLOCK_K
    interpret: bool = False
    xla_chunk: int = 512
    save_out: bool = False          # fused only: see fused_run_attention

    def __post_init__(self):
        # interpret mode is the CPU stand-in for the chip; on the chip
        # it would silently run the kernel body as slow XLA ops
        if self.interpret and jax.default_backend() == "tpu":
            raise ValueError("Pallas interpret mode requested on a TPU "
                             "backend; run the compiled kernel instead")


def _float0(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_attention(cfg: KernelConfig, q, k, v, seg_q, pos_q, seg_k,
                      pos_k):
    return fa.flash_attention_fwd(
        q, k, v, seg_q, pos_q, seg_k, pos_k, mask=cfg.mask,
        scale=cfg.scale, block_q=cfg.block_q, block_k=cfg.block_k,
        interpret=cfg.interpret)


def _pallas_fwd(cfg, q, k, v, seg_q, pos_q, seg_k, pos_k):
    o, lse = _pallas_attention(cfg, q, k, v, seg_q, pos_q, seg_k, pos_k)
    return (o, lse), (q, k, v, seg_q, pos_q, seg_k, pos_k, o, lse)


def _pallas_bwd(cfg, res, cot):
    q, k, v, seg_q, pos_q, seg_k, pos_k, o, lse = res
    do, dlse = cot
    dq, dk, dv = fa.flash_attention_bwd(
        q, k, v, seg_q, pos_q, seg_k, pos_k, o, lse, do, dlse,
        mask=cfg.mask, scale=cfg.scale, block_q=cfg.block_q,
        block_k=cfg.block_k, interpret=cfg.interpret)
    return (dq, dk, dv, _float0(seg_q), _float0(pos_q), _float0(seg_k),
            _float0(pos_k))


_pallas_attention.defvjp(_pallas_fwd, _pallas_bwd)


def block_attention(q, k, v, seg_q, pos_q, seg_k, pos_k, *,
                    mask=True, scale: float | None = None,
                    impl: str = "xla",
                    block_q: int = fa.DEFAULT_BLOCK_Q,
                    block_k: int = fa.DEFAULT_BLOCK_K,
                    interpret: bool = False,
                    xla_chunk: int = 512):
    """Normalized attention + lse over one (q, kv) pair of token ranges.

    q: [H, Sq, D]; k/v: [KH, Sk, D] → (o [H, Sq, D] f32, lse [H, Sq] f32).
    Merge partial results over disjoint KV with ``ref.merge_partials``.
    """
    mask = coerce_mask(mask)
    if impl == "pallas":
        cfg = KernelConfig(mask=mask, scale=scale, block_q=block_q,
                           block_k=block_k, interpret=interpret)
        return _pallas_attention(cfg, q, k, v, seg_q, pos_q, seg_k, pos_k)
    if impl == "xla":
        return ref.chunked_attention(q, k, v, seg_q, pos_q, seg_k, pos_k,
                                     mask, chunk=xla_chunk, scale=scale)
    if impl == "ref":
        return ref.reference_attention(q, k, v, seg_q, pos_q, seg_k, pos_k,
                                       mask, scale)
    raise ValueError(f"unknown impl {impl!r}")


merge_partials = ref.merge_partials
merge_many = ref.merge_many


# --------------------------------------------------------------------------
# fused run-granular attention (one launch per executor run)
# --------------------------------------------------------------------------
#
# Table pytree per run (all int32 except seg/pos which are int32 too):
#   step_q   [S]      q slot per step, q-slot-sorted
#   step_kv  [S]      extended-kv buffer row per step (same order)
#   q_seg/q_pos  [SL, bs]   per-slot metadata of the schedule layout
#   k_seg/k_pos  [S, bs]    per-step metadata of the consumed kv block
#   bwd_q/bwd_kv [S]        the same steps sorted by kv row (pallas only)
#   k_seg_b/k_pos_b [S, bs] per-step kv metadata in bwd order (pallas)


# checkpoint name of a fused run's (o, lse) residuals; the "dots" policy
# of ``models.transformer.apply_remat`` saves values under this name
FUSED_ATTN_OUT = "fcp_attn_out"


def _visited(idx, n: int):
    return jnp.zeros((n,), bool).at[idx].set(True)


def _fused_pallas_call(cfg: KernelConfig, qs, kxt, vxt, acc_o, acc_lse,
                       tabs):
    o, lse = fa.fused_flash_fwd(
        tabs["step_q"], tabs["step_kv"], qs, kxt, vxt,
        tabs["q_seg"], tabs["q_pos"], tabs["k_seg"], tabs["k_pos"],
        acc_o, acc_lse, mask=cfg.mask, scale=cfg.scale,
        block_q=cfg.block_q, block_k=cfg.block_k, interpret=cfg.interpret)
    # the kernel only writes slots the run visits; carry the rest over
    vis = _visited(tabs["step_q"], qs.shape[0])
    return (jnp.where(vis[:, None, None, None], o, acc_o),
            jnp.where(vis[:, None, None], lse, acc_lse))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_pallas(cfg: KernelConfig, qs, kxt, vxt, acc_o, acc_lse, tabs):
    return _fused_pallas_call(cfg, qs, kxt, vxt, acc_o, acc_lse, tabs)


def _fused_pl_fwd(cfg, qs, kxt, vxt, acc_o, acc_lse, tabs):
    o2, l2 = _fused_pallas(cfg, qs, kxt, vxt, acc_o, acc_lse, tabs)
    if cfg.save_out:
        # named on the residuals themselves: a remat policy that saves
        # the name keeps them, and the backward reruns no forward kernel
        o2 = checkpoint_name(o2, FUSED_ATTN_OUT)
        l2 = checkpoint_name(l2, FUSED_ATTN_OUT)
    return (o2, l2), (qs, kxt, vxt, acc_o, acc_lse, o2, l2, tabs)


def _fused_pl_bwd(cfg, res, cot):
    """Backward of one fused run.

    The run computes ``acc_out = merge(acc_in, partial_1, ...,
    partial_m)`` per q slot.  Differentiating the merge chain and
    substituting into the per-block flash backward makes every per-step
    weight cancel: each step's score gradient is
    ``ds = exp(s - L) ∘ (ḡ_o·v - Δ) · scale`` with the *run-final*
    ``L = acc_out_lse`` and ``Δ = ḡ_o·acc_out_o - ḡ_lse`` — i.e. the
    standard flash backward evaluated against the merged softmax stats,
    with no per-step lse saved.  The incoming accumulator is just one
    more partial, at weight ``w_a = exp(lse_in - L)``.
    """
    qs, kxt, vxt, acc_o, acc_lse, o2, l2, tabs = res
    g_o = cot[0].astype(jnp.float32)
    g_l = cot[1].astype(jnp.float32)

    w_a = jnp.exp(acc_lse - l2)                          # [SL, H, bs]
    d_acc_o = (w_a[..., None] * g_o).astype(acc_o.dtype)
    d_acc_lse = (w_a * (g_l + jnp.sum(g_o * acc_o, -1)
                        - jnp.sum(g_o * o2, -1))).astype(acc_lse.dtype)
    delta = jnp.sum(g_o * o2, -1) - g_l                  # [SL, H, bs]

    d_qs = fa.fused_flash_bwd_dq(
        tabs["step_q"], tabs["step_kv"], qs, kxt, vxt,
        tabs["q_seg"], tabs["q_pos"], tabs["k_seg"], tabs["k_pos"],
        l2, g_o, delta, mask=cfg.mask, scale=cfg.scale,
        block_q=cfg.block_q, block_k=cfg.block_k, interpret=cfg.interpret)
    visq = _visited(tabs["step_q"], qs.shape[0])
    d_qs = jnp.where(visq[:, None, None, None], d_qs, 0.0).astype(qs.dtype)

    d_k, d_v = fa.fused_flash_bwd_dkv(
        tabs["bwd_q"], tabs["bwd_kv"], qs, kxt, vxt,
        tabs["q_seg"], tabs["q_pos"], tabs["k_seg_b"], tabs["k_pos_b"],
        l2, g_o, delta, mask=cfg.mask, scale=cfg.scale,
        block_q=cfg.block_q, block_k=cfg.block_k, interpret=cfg.interpret)
    visk = _visited(tabs["bwd_kv"], kxt.shape[0])
    d_k = jnp.where(visk[:, None, None, None], d_k, 0.0).astype(kxt.dtype)
    d_v = jnp.where(visk[:, None, None, None], d_v, 0.0).astype(vxt.dtype)

    d_tabs = jax.tree.map(_float0, tabs)
    return d_qs, d_k, d_v, d_acc_o, d_acc_lse, d_tabs


_fused_pallas.defvjp(_fused_pl_fwd, _fused_pl_bwd)


def _fused_xla(qs, kxt, vxt, acc_o, acc_lse, tabs, *, mask: MaskSpec,
               scale: float | None, chunk: int):
    """Batched fallback: one vmapped attention over the run's steps and
    one scatter flash-merge into the accumulators (plain autodiff)."""
    idx = tabs["step_q"]
    kvi = tabs["step_kv"]
    q_r = jnp.take(qs, idx, axis=0)                       # [S, H, bs, D]
    k_r = jnp.take(kxt, kvi, axis=0)
    v_r = jnp.take(vxt, kvi, axis=0)
    sq = jnp.take(tabs["q_seg"], idx, axis=0)             # [S, bs]
    pq = jnp.take(tabs["q_pos"], idx, axis=0)
    o_p, lse_p = jax.vmap(
        lambda q, k, v, a, b, c, e: ref.chunked_attention(
            q, k, v, a, b, c, e, mask, chunk, scale))(
        q_r, k_r, v_r, sq, pq, tabs["k_seg"], tabs["k_pos"])

    # single-pass flash merge of {acc} ∪ {partials}: scatter-max the
    # stats, then one weighted scatter-add.  stop_gradient(m) is the
    # standard logsumexp trick — gradients flow through the exp terms.
    m = jax.lax.stop_gradient(acc_lse.at[idx].max(lse_p))
    w_a = jnp.exp(acc_lse - m)                            # [SL, H, bs]
    w_p = jnp.exp(lse_p - jnp.take(m, idx, axis=0))       # [S, H, bs]
    den = w_a.at[idx].add(w_p)                            # >= 1 (max term)
    num = (acc_o * w_a[..., None]).at[idx].add(o_p * w_p[..., None])
    return num / den[..., None], m + jnp.log(den)


def fused_run_attention(qs, kxt, vxt, acc_o, acc_lse, tabs, *,
                        mask=True, scale: float | None = None,
                        impl: str = "xla",
                        block_q: int = fa.DEFAULT_BLOCK_Q,
                        block_k: int = fa.DEFAULT_BLOCK_K,
                        interpret: bool = False,
                        xla_chunk: int = 512,
                        save_out: bool = False):
    """Fold one run of schedule steps into the flash accumulators.

    qs: [SL, H, bs, D] schedule-layout q; kxt/vxt: [EX, KH, bs, D]
    extended KV buffers; acc_o/acc_lse: [SL, H, bs(, D)] f32.  Returns
    the updated accumulators; slots the run does not visit pass through
    unchanged (so gradients flow across runs).

    ``save_out`` (pallas only) names the run's (o, lse) residuals
    :data:`FUSED_ATTN_OUT`, which the "dots" remat policy keeps: the
    backward then launches no second forward.  The executor sets it
    when the layer has one run; with more, every run would keep its own
    f32 accumulator.
    """
    mask = coerce_mask(mask)
    if impl == "pallas":
        cfg = KernelConfig(mask=mask, scale=scale, block_q=block_q,
                           block_k=block_k, interpret=interpret,
                           save_out=save_out)
        return _fused_pallas(cfg, qs, kxt, vxt, acc_o, acc_lse, tabs)
    if impl == "xla":
        return _fused_xla(qs, kxt, vxt, acc_o, acc_lse, tabs,
                          mask=mask, scale=scale, chunk=xla_chunk)
    raise ValueError(f"unknown fused impl {impl!r}")


def count_attention_launches(fn, *args) -> dict[str, int]:
    """Trace ``fn(*args)`` and count attention-op calls.

    Returns ``{"step": n_block_attention, "fused": n_fused_runs,
    "fused_saved": n_saved}`` — the per-worker per-layer launch
    accounting the fused executor is meant to shrink from ``n_steps`` to
    ``<= n_rounds + 1``; ``fused_saved`` counts the Pallas fused runs
    whose output is named for the remat policy to keep (one per one-run
    layer, none on a multi-run plan).  Tracing (not running) is enough:
    the executor unrolls its run loop in Python.
    """
    import jax as _jax
    calls = {"step": 0, "fused": 0, "fused_saved": 0}
    orig_b, orig_f = block_attention, fused_run_attention
    mod = sys.modules[__name__]

    def blk(*a, **kw):
        calls["step"] += 1
        return orig_b(*a, **kw)

    def fused(*a, **kw):
        calls["fused"] += 1
        if kw.get("save_out") and kw.get("impl") == "pallas":
            calls["fused_saved"] += 1
        return orig_f(*a, **kw)

    mod.block_attention, mod.fused_run_attention = blk, fused
    try:
        _jax.make_jaxpr(fn)(*args)
    finally:
        mod.block_attention, mod.fused_run_attention = orig_b, orig_f
    return calls
