"""Pallas TPU flash-attention kernel with segment-id varlen masking.

The per-device compute hot spot of FCP: attention between one (packed,
variable-length) query block and one KV block.  The paper uses
FlashAttention-3 "with minor modifications" (§5) — its modification is
exactly varlen/segment masking for packed blocks, which is what this
kernel provides natively through ``(segment_id, position)`` metadata.

TPU adaptation (DESIGN.md §2): tiles are MXU-aligned (128 multiples),
``BlockSpec``s stage q/k/v tiles HBM→VMEM, the kv grid axis is the
innermost (sequential) axis so the f32 accumulator lives in VMEM scratch
across kv tiles, and masking is computed on the fly from seg/pos tiles
(no O(Sq·Sk) mask in HBM).  Every kernel takes a static
:class:`~repro.masks.MaskSpec` (``_mask_tile`` adds the sliding-window
and chunk terms on top of the segment/causal rule); legacy
``causal: bool`` arguments coerce.

Layouts follow ``ref.py``: q [H, Sq, D], k/v [KH, Sk, D] → o [H, Sq, D],
lse [H, Sq].  Forward and backward (dq, dk, dv) kernels are provided;
``ops.py`` wires them into a ``custom_vjp``.

The second half of the file holds the *fused schedule-driven* kernels
(``fused_flash_fwd`` / ``fused_flash_bwd_dq`` / ``fused_flash_bwd_dkv``):
one launch per executor run, where scalar-prefetched step tables
(``step_q``, ``step_kv``) drive the BlockSpec index maps so KV tiles are
gathered straight from the extended receive buffer and the per-q-slot
online-softmax accumulator lives in VMEM scratch across every step the
run assigns to that slot (steps arrive q-slot-sorted from the schedule).
``acc_o``/``acc_lse`` touch HBM once per run instead of once per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..masks import coerce_mask
from .ref import NEG_INF, PAD_SEGMENT


def _vmem_scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _rows(x):
    """Per-row vector [..., n] -> [..., 1, n]: the kernel-side layout of
    segment ids, positions, lse and delta.  Mosaic needs the last two
    block dims divisible by (8, 128) or equal to the array's; a singleton
    second-minor axis makes a (1, block) tile of a row legal."""
    return x[..., None, :]


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def _mask_tile(seg_q, pos_q, seg_k, pos_k, mask):
    """Tile validity under a static MaskSpec: segment match plus the
    family's position predicate (shared with the oracle/ref paths)."""
    ok = (seg_q[:, None] == seg_k[None, :]) & (seg_q[:, None] != PAD_SEGMENT)
    return ok & mask.visible(pos_q[:, None], pos_k[None, :])


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, mask, n_kv_tiles: int):
    j = pl.program_id(2)                       # kv tile (innermost, seq.)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)           # [bq, d]
    k = k_ref[0].astype(jnp.float32)           # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = _mask_tile(sq_ref[0], pq_ref[0], sk_ref[0], pk_ref[0], mask)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                        # [bq]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(s - m_cur[:, None]), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(j == n_kv_tiles - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.maximum(l, 1e-37)
        o_ref[0] = jnp.where(l[:, None] > 0, acc_ref[...] / safe[:, None],
                             0.0).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l > 0, m_ref[...] + jnp.log(safe),
                                  NEG_INF)


@functools.partial(jax.jit, static_argnames=(
    "mask", "scale", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, seg_q, pos_q, seg_k, pos_k, *,
                        mask=True, scale: float | None = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """Pallas forward. Returns (o [H,Sq,D] f32, lse [H,Sq] f32)."""
    mask = coerce_mask(mask)
    h, sq, d = q.shape
    kh, sk, _ = k.shape
    assert h % kh == 0, (h, kh)
    group = h // kh
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)
    n_q = sq // block_q
    n_k = sk // block_k
    grid = (h, n_q, n_k)

    kernel = functools.partial(_fwd_kernel, scale=scale, mask=mask,
                               n_kv_tiles=n_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, i, j: (hh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hh, i, j, g=group: (hh // g, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hh, i, j, g=group: (hh // g, j, 0)),
            pl.BlockSpec((1, block_q), lambda hh, i, j: (0, i)),
            pl.BlockSpec((1, block_q), lambda hh, i, j: (0, i)),
            pl.BlockSpec((1, block_k), lambda hh, i, j: (0, j)),
            pl.BlockSpec((1, block_k), lambda hh, i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, i, j: (hh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda hh, i, j: (hh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            # f32 accumulators living across the kv grid dimension
            _vmem_scratch((block_q, d)),
            _vmem_scratch((block_q,)),
            _vmem_scratch((block_q,)),
        ],
        interpret=interpret,
    )(q, k, v, _rows(seg_q), _rows(pos_q), _rows(seg_k), _rows(pos_k))
    return o, lse[:, 0]


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                   lse_ref, do_ref, delta_ref, dlse_ref,
                   dq_ref, dq_acc,
                   *, scale: float, mask, n_kv_tiles: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    dlse = dlse_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = _mask_tile(sq_ref[0], pq_ref[0], sk_ref[0], pk_ref[0], mask)
    p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
    dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds = p * (dov - delta[:, None] + dlse[:, None]) * scale
    dq_acc[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == n_kv_tiles - 1)
    def _done():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                    lse_ref, do_ref, delta_ref, dlse_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale: float, mask, n_q_tiles: int,
                    group: int):
    # grid = (kh, n_k, group, n_q): the (group, q-tile) sweep is innermost
    # so each dk/dv output block (kh, j) is visited contiguously and the
    # scratch accumulators span exactly one kv tile's lifetime.
    i = pl.program_id(3)                        # q tile (innermost)
    g = pl.program_id(2)                        # group member of kv head

    @pl.when(jnp.logical_and(i == 0, g == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    dlse = dlse_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = _mask_tile(sq_ref[0], pq_ref[0], sk_ref[0], pk_ref[0], mask)
    p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)       # [bq, bk]
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds = p * (dov - delta[:, None] + dlse[:, None]) * scale
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(i == n_q_tiles - 1, g == group - 1))
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "mask", "scale", "block_q", "block_k", "interpret"))
def flash_attention_bwd(q, k, v, seg_q, pos_q, seg_k, pos_k, o, lse,
                        do, dlse, *, mask=True,
                        scale: float | None = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """Pallas backward: returns (dq, dk, dv) in input dtypes.

    ``dlse`` is the cotangent of the lse output (non-zero when the result
    participates in a downstream flash merge — the FCP executor's case).
    """
    mask = coerce_mask(mask)
    h, sq, d = q.shape
    kh, sk, _ = k.shape
    group = h // kh
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q, n_k = sq // block_q, sk // block_k
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)    # [H, Sq]
    row_args = (q, k, v, _rows(seg_q), _rows(pos_q), _rows(seg_k),
                _rows(pos_k), _rows(lse), do, _rows(delta), _rows(dlse))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, mask=mask,
                          n_kv_tiles=n_k),
        grid=(h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, i, j: (hh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hh, i, j, g=group: (hh // g, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hh, i, j, g=group: (hh // g, j, 0)),
            pl.BlockSpec((1, block_q), lambda hh, i, j: (0, i)),
            pl.BlockSpec((1, block_q), lambda hh, i, j: (0, i)),
            pl.BlockSpec((1, block_k), lambda hh, i, j: (0, j)),
            pl.BlockSpec((1, block_k), lambda hh, i, j: (0, j)),
            pl.BlockSpec((1, 1, block_q), lambda hh, i, j: (hh, 0, i)),
            pl.BlockSpec((1, block_q, d), lambda hh, i, j: (hh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda hh, i, j: (hh, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda hh, i, j: (hh, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda hh, i, j: (hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[_vmem_scratch((block_q, d))],
        interpret=interpret,
    )(*row_args)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, mask=mask,
                          n_q_tiles=n_q, group=group),
        grid=(kh, n_k, group, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda kk, j, g, i, gr=group: (kk * gr + g, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda kk, j, g, i: (kk, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda kk, j, g, i: (kk, j, 0)),
            pl.BlockSpec((1, block_q), lambda kk, j, g, i: (0, i)),
            pl.BlockSpec((1, block_q), lambda kk, j, g, i: (0, i)),
            pl.BlockSpec((1, block_k), lambda kk, j, g, i: (0, j)),
            pl.BlockSpec((1, block_k), lambda kk, j, g, i: (0, j)),
            pl.BlockSpec((1, 1, block_q),
                         lambda kk, j, g, i, gr=group: (kk * gr + g, 0, i)),
            pl.BlockSpec((1, block_q, d),
                         lambda kk, j, g, i, gr=group: (kk * gr + g, i, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda kk, j, g, i, gr=group: (kk * gr + g, 0, i)),
            pl.BlockSpec((1, 1, block_q),
                         lambda kk, j, g, i, gr=group: (kk * gr + g, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda kk, j, g, i: (kk, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda kk, j, g, i: (kk, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem_scratch((block_k, d)),
                        _vmem_scratch((block_k, d))],
        interpret=interpret,
    )(*row_args)

    return dq, dk, dv


# --------------------------------------------------------------------------
# fused schedule-driven kernels: one launch per executor run
# --------------------------------------------------------------------------
#
# Inputs are whole executor buffers (qs [SL, H, bs, D], kxt/vxt
# [EX, KH, bs, D], accumulators [SL, H, bs(, D)]) plus per-run step
# tables.  The tables are scalar-prefetched so every BlockSpec index map
# can gather the tile its grid step needs: the q/acc maps read
# ``step_q[s]``, the kv maps read ``step_kv[s]``.  The kv axis is the
# innermost grid dimension and steps sharing a q slot are contiguous
# (schedule sorts them), so the (acc, m, l) scratch state carries one q
# slot's accumulator across all its KV blocks without touching HBM.
#
# Because only the slots a run visits are written, callers must combine
# kernel outputs with the incoming accumulators (`where(visited, ...)`)
# — done in ``ops.fused_run_attention`` (avoids relying on pallas
# input/output aliasing semantics in interpret mode).


def _fused_fwd_kernel(sq_tab, skv_tab, q_ref, k_ref, v_ref, qs_ref, qp_ref,
                      ks_ref, kp_ref, ai_o_ref, ai_l_ref,
                      o_ref, lse_ref,
                      acc_ref, m_ref, l_ref,
                      *, scale: float, mask, n_kv_tiles: int,
                      n_steps: int):
    s = pl.program_id(2)                       # run step
    kj = pl.program_id(3)                      # kv tile (innermost, seq.)
    slot = sq_tab[s]
    prev = sq_tab[jnp.maximum(s - 1, 0)]
    nxt = sq_tab[jnp.minimum(s + 1, n_steps - 1)]
    first = jnp.logical_or(s == 0, slot != prev)
    last = jnp.logical_or(s == n_steps - 1, slot != nxt)

    @pl.when(jnp.logical_and(first, kj == 0))
    def _seed():
        # incoming accumulator == one normalized partial of weight 1:
        # o = acc/l, lse = m + log l  ⇒  (acc, m, l) = (o_in, lse_in, 1)
        acc_ref[...] = ai_o_ref[0, 0].astype(jnp.float32)
        m_ref[...] = ai_l_ref[0, 0, 0]
        l_ref[...] = jnp.ones_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)        # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)        # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    valid = _mask_tile(qs_ref[0, 0], qp_ref[0, 0], ks_ref[0, 0],
                       kp_ref[0, 0], mask)
    sc = jnp.where(valid, sc, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(sc - m_cur[:, None]), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(jnp.logical_and(last, kj == n_kv_tiles - 1))
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)     # >= alpha·1 + mass > 0
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=(
    "mask", "scale", "block_q", "block_k", "interpret"))
def fused_flash_fwd(step_q, step_kv, qs, kxt, vxt, q_seg, q_pos,
                    k_seg, k_pos, acc_o, acc_lse, *,
                    mask=True, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """One fused launch over a run of (q slot, kv slot) steps.

    step_q/step_kv: [S] int32, q-slot-sorted; qs: [SL, H, bs, D];
    kxt/vxt: [EX, KH, bs, D]; q_seg/q_pos: [SL, bs]; k_seg/k_pos:
    [S, bs] (per-step metadata of the consumed kv block); acc_o/acc_lse:
    [SL, H, bs(, D)].  Returns (o, lse) buffers in which only the slots
    named by ``step_q`` are written — combine with the incoming
    accumulators via the visited mask.
    """
    mask = coerce_mask(mask)
    sl, h, bs, d = qs.shape
    kh = kxt.shape[1]
    group = h // kh
    n_steps = step_q.shape[0]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, bs)
    block_k = min(block_k, bs)
    assert bs % block_q == 0 and bs % block_k == 0, (bs, block_q, block_k)
    n_qi = bs // block_q
    n_kj = bs // block_k
    grid = (h, n_qi, n_steps, n_kj)

    kernel = functools.partial(
        _fused_fwd_kernel, scale=scale, mask=mask, n_kv_tiles=n_kj,
        n_steps=n_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda hh, qi, s, kj, sq, skv, g=group:
                         (skv[s], hh // g, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda hh, qi, s, kj, sq, skv, g=group:
                         (skv[s], hh // g, kj, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], 0, qi)),
            pl.BlockSpec((1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], 0, qi)),
            pl.BlockSpec((1, 1, block_k),
                         lambda hh, qi, s, kj, sq, skv: (s, 0, kj)),
            pl.BlockSpec((1, 1, block_k),
                         lambda hh, qi, s, kj, sq, skv: (s, 0, kj)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, 0, qi)),
        ],
        scratch_shapes=[
            _vmem_scratch((block_q, d)),
            _vmem_scratch((block_q,)),
            _vmem_scratch((block_q,)),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((sl, h, bs, d), jnp.float32),
            jax.ShapeDtypeStruct((sl, h, 1, bs), jnp.float32),
        ],
        interpret=interpret,
    )(step_q, step_kv, qs, kxt, vxt, _rows(q_seg), _rows(q_pos),
      _rows(k_seg), _rows(k_pos), acc_o, _rows(acc_lse))
    return o, lse[:, :, 0]


def _fused_dq_kernel(sq_tab, skv_tab, q_ref, k_ref, v_ref, qs_ref, qp_ref,
                     ks_ref, kp_ref, lse_ref, go_ref, dl_ref,
                     dq_ref, dq_acc,
                     *, scale: float, mask, n_kv_tiles: int,
                     n_steps: int):
    # gradients of the whole run chain collapse onto the run-final
    # (o, lse): ds = exp(s - L_final) ∘ (ḡ_o·v - Δ),
    # with Δ = ḡ_o·o_out - ḡ_lse
    # (per q row) — the flash backward with the *merged* softmax stats.
    s = pl.program_id(2)
    kj = pl.program_id(3)
    slot = sq_tab[s]
    prev = sq_tab[jnp.maximum(s - 1, 0)]
    nxt = sq_tab[jnp.minimum(s + 1, n_steps - 1)]
    first = jnp.logical_or(s == 0, slot != prev)
    last = jnp.logical_or(s == n_steps - 1, slot != nxt)

    @pl.when(jnp.logical_and(first, kj == 0))
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    go = go_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0]
    delta = dl_ref[0, 0, 0]

    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    valid = _mask_tile(qs_ref[0, 0], qp_ref[0, 0], ks_ref[0, 0],
                       kp_ref[0, 0], mask)
    p = jnp.where(valid, jnp.exp(sc - lse[:, None]), 0.0)
    dov = jax.lax.dot_general(go, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds = p * (dov - delta[:, None]) * scale
    dq_acc[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(last, kj == n_kv_tiles - 1))
    def _done():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "mask", "scale", "block_q", "block_k", "interpret"))
def fused_flash_bwd_dq(step_q, step_kv, qs, kxt, vxt, q_seg, q_pos,
                       k_seg, k_pos, lse, go, delta, *,
                       mask=True, scale: float | None = None,
                       block_q: int = DEFAULT_BLOCK_Q,
                       block_k: int = DEFAULT_BLOCK_K,
                       interpret: bool = False):
    """d_qs of a fused run.  ``lse``: run-final acc_lse [SL, H, bs];
    ``go``: d(acc_o) [SL, H, bs, D]; ``delta``: ḡ_o·o_out - ḡ_lse
    [SL, H, bs].  Tables are the forward (q-slot-sorted) ones; each q
    slot's dq tile accumulates in VMEM across its contiguous steps and is
    written once.  Unvisited slots are left unwritten — mask outside.
    """
    mask = coerce_mask(mask)
    sl, h, bs, d = qs.shape
    kh = kxt.shape[1]
    group = h // kh
    n_steps = step_q.shape[0]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, bs)
    block_k = min(block_k, bs)
    assert bs % block_q == 0 and bs % block_k == 0, (bs, block_q, block_k)
    n_qi = bs // block_q
    n_kj = bs // block_k
    grid = (h, n_qi, n_steps, n_kj)

    kernel = functools.partial(
        _fused_dq_kernel, scale=scale, mask=mask, n_kv_tiles=n_kj,
        n_steps=n_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda hh, qi, s, kj, sq, skv, g=group:
                         (skv[s], hh // g, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda hh, qi, s, kj, sq, skv, g=group:
                         (skv[s], hh // g, kj, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], 0, qi)),
            pl.BlockSpec((1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], 0, qi)),
            pl.BlockSpec((1, 1, block_k),
                         lambda hh, qi, s, kj, sq, skv: (s, 0, kj)),
            pl.BlockSpec((1, 1, block_k),
                         lambda hh, qi, s, kj, sq, skv: (s, 0, kj)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, 0, qi)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda hh, qi, s, kj, sq, skv: (sq[s], hh, 0, qi)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d),
            lambda hh, qi, s, kj, sq, skv: (sq[s], hh, qi, 0)),
        scratch_shapes=[_vmem_scratch((block_q, d))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((sl, h, bs, d), jnp.float32),
        interpret=interpret,
    )(step_q, step_kv, qs, kxt, vxt, _rows(q_seg), _rows(q_pos),
      _rows(k_seg), _rows(k_pos), _rows(lse), go, _rows(delta))


def _fused_dkv_kernel(bq_tab, bkv_tab, q_ref, k_ref, v_ref, qs_ref, qp_ref,
                      ks_ref, kp_ref, lse_ref, go_ref, dl_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc,
                      *, scale: float, mask, n_q_tiles: int,
                      group: int, n_steps: int):
    # grid = (kh, n_kj, S, group, n_qi): steps are kv-slot-sorted, so for
    # a fixed kv tile the (s, g, i) sweep visits each extended-buffer row
    # contiguously and dk/dv accumulate in VMEM across every consumer.
    s = pl.program_id(2)
    g = pl.program_id(3)
    i = pl.program_id(4)
    row = bkv_tab[s]
    prev = bkv_tab[jnp.maximum(s - 1, 0)]
    nxt = bkv_tab[jnp.minimum(s + 1, n_steps - 1)]
    first = jnp.logical_or(s == 0, row != prev)
    last = jnp.logical_or(s == n_steps - 1, row != nxt)

    @pl.when(jnp.logical_and(first, jnp.logical_and(g == 0, i == 0)))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    go = go_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0]
    delta = dl_ref[0, 0, 0]

    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    valid = _mask_tile(qs_ref[0, 0], qp_ref[0, 0], ks_ref[0, 0],
                       kp_ref[0, 0], mask)
    p = jnp.where(valid, jnp.exp(sc - lse[:, None]), 0.0)      # [bq, bk]
    dv_acc[...] += jax.lax.dot_general(
        p, go, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dov = jax.lax.dot_general(go, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds = p * (dov - delta[:, None]) * scale
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(
        last, jnp.logical_and(g == group - 1, i == n_q_tiles - 1)))
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "mask", "scale", "block_q", "block_k", "interpret"))
def fused_flash_bwd_dkv(bwd_q, bwd_kv, qs, kxt, vxt, q_seg, q_pos,
                        k_seg, k_pos, lse, go, delta, *,
                        mask=True, scale: float | None = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """(d_kxt, d_vxt) of a fused run, scattered to extended-buffer rows.

    ``bwd_q``/``bwd_kv`` are the run's steps sorted by kv slot;
    ``k_seg``/``k_pos`` are per-step metadata in that order.  ``lse``,
    ``go``, ``delta`` as in :func:`fused_flash_bwd_dq`.  Rows no step
    consumes are left unwritten — mask outside.
    """
    mask = coerce_mask(mask)
    sl, h, bs, d = qs.shape
    ex, kh = kxt.shape[0], kxt.shape[1]
    group = h // kh
    n_steps = bwd_q.shape[0]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, bs)
    block_k = min(block_k, bs)
    assert bs % block_q == 0 and bs % block_k == 0, (bs, block_q, block_k)
    n_qi = bs // block_q
    n_kj = bs // block_k
    grid = (kh, n_kj, n_steps, group, n_qi)

    kernel = functools.partial(
        _fused_dkv_kernel, scale=scale, mask=mask, n_q_tiles=n_qi,
        group=group, n_steps=n_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda kk, kj, s, g, i, bq, bkv, gr=group:
                         (bq[s], kk * gr + g, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda kk, kj, s, g, i, bq, bkv: (bkv[s], kk, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda kk, kj, s, g, i, bq, bkv: (bkv[s], kk, kj, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda kk, kj, s, g, i, bq, bkv: (bq[s], 0, i)),
            pl.BlockSpec((1, 1, block_q),
                         lambda kk, kj, s, g, i, bq, bkv: (bq[s], 0, i)),
            pl.BlockSpec((1, 1, block_k),
                         lambda kk, kj, s, g, i, bq, bkv: (s, 0, kj)),
            pl.BlockSpec((1, 1, block_k),
                         lambda kk, kj, s, g, i, bq, bkv: (s, 0, kj)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda kk, kj, s, g, i, bq, bkv, gr=group:
                         (bq[s], kk * gr + g, 0, i)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda kk, kj, s, g, i, bq, bkv, gr=group:
                         (bq[s], kk * gr + g, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda kk, kj, s, g, i, bq, bkv, gr=group:
                         (bq[s], kk * gr + g, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda kk, kj, s, g, i, bq, bkv: (bkv[s], kk, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda kk, kj, s, g, i, bq, bkv: (bkv[s], kk, kj, 0)),
        ],
        scratch_shapes=[_vmem_scratch((block_k, d)),
                        _vmem_scratch((block_k, d))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((ex, kh, bs, d), jnp.float32),
            jax.ShapeDtypeStruct((ex, kh, bs, d), jnp.float32),
        ],
        interpret=interpret,
    )(bwd_q, bwd_kv, qs, kxt, vxt, _rows(q_seg), _rows(q_pos),
      _rows(k_seg), _rows(k_pos), _rows(lse), go, _rows(delta))
