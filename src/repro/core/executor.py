"""FCP distributed attention executor (paper §4.2–§4.3, TPU-native).

Runs a host-built :class:`~repro.core.schedule.Schedule` inside
``jax.shard_map``:

* **transparent reshuffle** — ppermute matchings move (q, k, v) blocks
  from the user/stream layout to the schedule layout (and ``o`` back);
* **software-pipelined rounds** — the round loop has two modes.  Serial
  (``spec.overlap`` off): per coalesced round ``r`` the kernel issues the
  round's ``lax.ppermute`` group(s) (each group a partial permutation ==
  congestion-free, Lemma 1, shipping a stack of up to ``C`` KV blocks —
  the §4.2 bottom-up coalescer), computes run ``r``, then commits the
  arrivals.  Overlap (``spec.overlap`` on — the double-buffered pipeline,
  paper §5): round ``r+1``'s sends are issued *before* run ``r``'s
  compute, gathered from an **immutable snapshot of the local KV slots**
  (sends only ever read local slots or the zero trash row, never the
  receive region commits scatter into) — severing the false dataflow
  edge ``ship(r+1) ← commit(r)`` that serializes the serial loop — and
  arrivals land in **double-buffered receive slots** (the buffer-parity
  allocation of ``planner.allocate_recv_slots``: consecutive rounds
  commit into disjoint slot halves), so a commit never waits on an
  in-flight send and XLA's async collective scheduler hides the wire
  behind the fused kernel (``docs/overlap.md`` has the timeline);
* **compute runs** — the schedule groups the steps between two arrival
  commits into a *run*.  The fused impls (``fused`` / ``fused_xla``)
  issue ONE attention launch per run (``kernels.ops.fused_run_attention``:
  step tables drive the KV gathers, flash accumulators touch HBM once
  per run); the per-step impls (``pallas`` / ``xla``) run one
  ``block_attention`` + merge per (q-slot, kv-slot) step;
* received blocks land in a live-range-colored buffer (planner §4.2),
  keeping receive memory at max-live depth;
* every ppermute payload travels in the schedule's **wire format**
  (``StaticSpec.wire`` → ``runtime/wire.ship``): encoded — f32
  passthrough / bf16 / int8 with per-(block, head) scales — right
  before the collective and decoded into the compute dtype on arrival,
  so kernels and merge math are untouched and only the wire is lossy
  (forward and backward alike; f32 stays bit-exact).

Everything is differentiable: the backward pass reverses the permutations
automatically (ppermute transpose) — FCP's backward is the same schedule
run in reverse, as in the paper.

For the layer-pipelined reshuffle (``docs/overlap.md``),
``fcp_attention(..., layout="sched")`` consumes q/k/v already resident
in the schedule layout and returns o in the schedule layout — skipping
the per-layer Q/K/V reshuffle and O restore entirely — while
``fcp_reshuffle`` moves the *hidden state* (any per-token tensor)
between layouts once per layer group instead of once per layer.

Also provides ``cp_decode_attention``: context-parallel decode where the
KV cache is sharded along sequence and partials merge with a psum-flash
reduction (Yang et al. 2025b style; used by decode_32k / long_500k
shapes).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from ..kernels.ref import NEG_INF
from ..runtime import wire as wirelib
from .schedule import PlanArrays, Schedule, StaticSpec

# every ppermute payload is [rows, heads, block, head_dim]; quantized
# wire formats carry one scale per (row, head) — per-(block, kv-head)
# for the KV stacks — so an outlier head cannot wash out a block
_SCALE_AXES = (-2, -1)


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    # "xla" / "pallas": one block_attention + merge per schedule step.
    # "fused_xla" / "fused": ONE launch per run (vmap-batched XLA /
    # schedule-table-driven Pallas kernel) — accumulators touch HBM once
    # per run instead of once per step.
    impl: str = "xla"
    block_q: int = 256
    block_k: int = 256
    interpret: bool = False         # pallas interpret mode (CPU tests)
    xla_chunk: int = 512
    out_dtype: str | None = None    # e.g. "bfloat16": halve restore bytes

    @property
    def fused(self) -> bool:
        return self.impl in ("fused", "fused_xla")


def plan_tables(arrays: PlanArrays) -> dict[str, jax.Array]:
    """numpy plan tables → device arrays (leading dim = CP workers)."""
    return {f.name: jnp.asarray(getattr(arrays, f.name))
            for f in dataclasses.fields(arrays)}


def _gather_rows(buf: jax.Array, idx: jax.Array) -> jax.Array:
    """rows ``buf[idx]`` with ``idx == -1`` → zeros."""
    safe = jnp.clip(idx, 0, buf.shape[0] - 1)
    out = jnp.take(buf, safe, axis=0)
    mask = (idx >= 0).reshape((-1,) + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, 0.0)


def _dyn_row(buf: jax.Array, i: jax.Array) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(buf, i, 1, axis=0)


def _set_row(buf: jax.Array, row: jax.Array, i: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(buf, row, i, axis=0)


def _fcp_local(q, k, v, t, *, spec: StaticSpec, cp_axis: str,
               cfg: ExecConfig, layout: str = "stream"):
    """Per-device executor body.

    q: [1, tpw, hq, d]; k/v: [1, tpw, kh, d]; ``t``: local plan tables
    (leading dim 1).  Returns o: [1, tpw, hq, d] f32.

    ``layout="stream"`` (default) reshuffles q/k/v from the user layout
    into the schedule layout and restores o; ``layout="sched"`` takes
    q/k/v already in the schedule layout and returns o in the schedule
    layout (the layer-pipelined path: the caller moved the hidden state
    once per layer group via :func:`fcp_reshuffle`).
    """
    bs, slots, ext = spec.block_size, spec.slots, spec.ext_slots
    tpw = slots * bs
    hq, d = q.shape[2], q.shape[3]
    kh = k.shape[2]
    fmt = spec.wire

    def ship(payload, perm):
        # encode -> ppermute -> decode (runtime/wire.py): the payload
        # travels in the schedule's wire format and arrives back in its
        # compute dtype; f32 is a bit-exact passthrough of ppermute
        return wirelib.ship(payload, tuple(perm), cp_axis, fmt,
                            _SCALE_AXES)

    # blk_* are replicated (shared mask metadata); the rest are per-worker
    t = {k_: (v_ if k_.startswith("blk_") else v_[0])
         for k_, v_ in t.items()}

    # user layout -> [slots, heads, bs, d] (head-leading kernel layout)
    def frame(x, h):
        return (x.reshape(slots, bs, h, d).transpose(0, 2, 1, 3))

    q_u, k_u, v_u = frame(q[0], hq), frame(k[0], kh), frame(v[0], kh)

    # ---- transparent reshuffle: stream layout -> schedule layout ----------
    def with_trash(x):
        return jnp.concatenate([x, jnp.zeros_like(x[:1])], axis=0)

    if layout == "sched":
        # layer-pipelined path: inputs are already schedule-resident
        # (the hidden state moved at the layer-group boundary), so the
        # per-layer Q/K/V reshuffle vanishes
        qs, ks, vs = with_trash(q_u), with_trash(k_u), with_trash(v_u)
    else:
        qs = with_trash(_gather_rows(q_u, t["resh_local_src"]))
        ks = with_trash(_gather_rows(k_u, t["resh_local_src"]))
        vs = with_trash(_gather_rows(v_u, t["resh_local_src"]))
        # senders gather through a trash row: idle lanes ship zeros
        q_ut, k_ut, v_ut = (with_trash(q_u), with_trash(k_u),
                            with_trash(v_u))
        for r in range(spec.n_resh_rounds):
            snd = t["resh_send_slot"][r]             # [S2] payload rows
            dst = t["resh_dst_slot"][r]
            off = 0
            for g in spec.resh_rounds[r].groups:
                # rows the worker doesn't participate in gather/write trash
                idx = snd[off:off + g.rows]
                payload = jnp.concatenate([
                    _gather_rows(q_ut, idx),
                    _gather_rows(k_ut, idx),
                    _gather_rows(v_ut, idx)], axis=1)  # [rows, hq+2kh, ...]
                recv = ship(payload, g.perm)
                # one scatter per group (idle rows land on the trash row)
                didx = dst[off:off + g.rows]
                qs = qs.at[didx].set(recv[:, :hq])
                ks = ks.at[didx].set(recv[:, hq:hq + kh])
                vs = vs.at[didx].set(recv[:, hq + kh:])
                off += g.rows

    # ---- extended KV buffer (local slots + colored receive slots + trash) -
    zpad = jnp.zeros((ext + 1, kh, bs, d), ks.dtype)
    kxt = jnp.concatenate([ks[:slots], zpad], axis=0)
    vxt = jnp.concatenate([vs[:slots], zpad], axis=0)
    # kv seg/pos of the block consumed at each step comes from the
    # host-precomputed step tables (only K/V bytes travel the network)

    acc_o = jnp.zeros((slots + 1, hq, bs, d), jnp.float32)
    acc_lse = jnp.full((slots + 1, hq, bs), NEG_INF, jnp.float32)

    if cfg.fused:
        # per-slot / per-step mask metadata, gathered once per call
        q_seg = jnp.take(t["blk_seg"], t["sched_blk"], axis=0)
        q_pos = jnp.take(t["blk_pos"], t["sched_blk"], axis=0)
        k_seg = jnp.take(t["blk_seg"], t["step_kv_blk"], axis=0)
        k_pos = jnp.take(t["blk_pos"], t["step_kv_blk"], axis=0)
        if cfg.impl == "fused":
            k_seg_b = jnp.take(t["blk_seg"], t["bwd_kv_blk"], axis=0)
            k_pos_b = jnp.take(t["blk_pos"], t["bwd_kv_blk"], axis=0)

    if spec.overlap and spec.n_rounds:
        # immutable send sources: send rows only ever name LOCAL slots
        # (< slots) or the trash row — never the receive region that
        # commits scatter into — so payloads gathered from this frozen
        # snapshot are bitwise-identical to gathering from kxt/vxt,
        # while severing the false dataflow edge ship(r+1) <- commit(r)
        # that forces the serial loop to take turns with the wire
        ksrc = jnp.concatenate([ks[:slots], zpad[:1]], axis=0)
        vsrc = jnp.concatenate([vs[:slots], zpad[:1]], axis=0)

    def issue(r):
        # one ppermute per group; each ships a stack of up to C KV
        # blocks (the §4.2 coalescer).  Returns [(row offset, group,
        # shipped payload), ...] for the commit of round r.
        snd = t["send_slot"][r]                     # [S] payload rows
        out = []
        off = 0
        for g in spec.comm_rounds[r].groups:
            idx = snd[off:off + g.rows]
            if spec.overlap:
                # remap the trash index (slots + ext) onto the frozen
                # zero row; -1 padding stays -1 (zeros via _gather_rows)
                idx = jnp.minimum(idx, slots)
                payload = jnp.concatenate(
                    [_gather_rows(ksrc, idx), _gather_rows(vsrc, idx)],
                    axis=1)                         # [rows, 2kh, bs, d]
            else:
                payload = jnp.concatenate(
                    [_gather_rows(kxt, idx), _gather_rows(vxt, idx)],
                    axis=1)
            out.append((off, g, ship(payload, g.perm)))
            off += g.rows
        return out

    # run r computes between the ppermute issue and the arrival commit
    # of round r: consumers of round r's blocks sit in runs > r (§4.2).
    # Serial mode issues round r at the top of iteration r; overlap mode
    # runs one round ahead — round 0 is issued in a prologue and
    # iteration r issues round r+1 BEFORE run r's compute, so the
    # collective is in flight while the kernel works and the commit
    # below never waits on an in-flight send (double-buffered receive
    # slots keep the early commit from racing run r's reads).
    pending = issue(0) if (spec.overlap and spec.n_rounds) else []
    for r in range(spec.n_runs):
        if spec.overlap:
            arrivals = pending if r < spec.n_rounds else []
            pending = issue(r + 1) if r + 1 < spec.n_rounds else []
        else:
            arrivals = issue(r) if r < spec.n_rounds else []
        lo, hi = spec.run_starts[r], spec.run_starts[r + 1]
        if hi > lo and cfg.fused:
            # ONE fused launch for the whole run: step tables drive the
            # KV gathers, accumulators touch HBM once per run.
            tabs = {"step_q": t["step_q"][lo:hi],
                    "step_kv": t["step_kv"][lo:hi],
                    "q_seg": q_seg, "q_pos": q_pos,
                    "k_seg": k_seg[lo:hi], "k_pos": k_pos[lo:hi]}
            if cfg.impl == "fused":
                tabs.update(bwd_q=t["bwd_q"][lo:hi],
                            bwd_kv=t["bwd_kv"][lo:hi],
                            k_seg_b=k_seg_b[lo:hi],
                            k_pos_b=k_pos_b[lo:hi])
            acc_o, acc_lse = ops.fused_run_attention(
                qs, kxt, vxt, acc_o, acc_lse, tabs, mask=spec.mask,
                impl="pallas" if cfg.impl == "fused" else "xla",
                block_q=cfg.block_q, block_k=cfg.block_k,
                interpret=cfg.interpret, xla_chunk=cfg.xla_chunk)
        elif hi > lo:
            for step in range(lo, hi):
                qslot = t["step_q"][step]
                kvslot = t["step_kv"][step]
                qi = _dyn_row(qs, qslot)[0]              # [hq, bs, d]
                qblk = _dyn_row(t["sched_blk"], qslot)[0]
                sq_m = _dyn_row(t["blk_seg"], qblk)[0]
                pq_m = _dyn_row(t["blk_pos"], qblk)[0]
                kvblk = t["step_kv_blk"][step]
                sk_m = _dyn_row(t["blk_seg"], kvblk)[0]
                pk_m = _dyn_row(t["blk_pos"], kvblk)[0]
                ki = _dyn_row(kxt, kvslot)[0]
                vi = _dyn_row(vxt, kvslot)[0]
                o_p, lse_p = ops.block_attention(
                    qi, ki, vi, sq_m, pq_m, sk_m, pk_m,
                    mask=spec.mask, impl=cfg.impl, block_q=cfg.block_q,
                    block_k=cfg.block_k, interpret=cfg.interpret,
                    xla_chunk=cfg.xla_chunk)
                o_old = _dyn_row(acc_o, qslot)[0]
                l_old = _dyn_row(acc_lse, qslot)[0]
                o_new, l_new = ops.merge_partials(o_old, l_old, o_p, lse_p)
                acc_o = _set_row(acc_o, o_new[None], qslot)
                acc_lse = _set_row(acc_lse, l_new[None], qslot)
        if arrivals:
            # commit the arrivals after compute: consumers sit in later
            # runs (round granularity — the §4.2 consumer constraint)
            dst = t["recv_slot"][r]                     # [S] buffer slots
            for off, g, recv in arrivals:
                didx = dst[off:off + g.rows]
                kxt = kxt.at[didx].set(recv[:, :kh])
                vxt = vxt.at[didx].set(recv[:, kh:])

    # ---- restore: schedule layout -> stream layout -------------------------
    if cfg.out_dtype is not None:
        # cast before the restore ppermutes: halves restore traffic
        acc_o = acc_o.astype(jnp.dtype(cfg.out_dtype))
    if layout == "sched":
        # layer-pipelined path: the caller keeps consuming the schedule
        # layout, so o stays put (no restore ppermutes at all)
        o = acc_o[:slots].transpose(0, 2, 1, 3).reshape(tpw, hq, d)
        return o[None]
    o_u = with_trash(_gather_rows(acc_o[:slots + 1], t["restore_local_src"]))
    for r in range(spec.n_resh_rounds):
        snd = t["restore_send_slot"][r]
        dst = t["restore_dst_slot"][r]
        off = 0
        for g in spec.resh_rounds[r].groups:
            # reversed partial permutation is a partial permutation
            perm = tuple((d_, s_) for s_, d_ in g.perm)
            payload = _gather_rows(acc_o, snd[off:off + g.rows])
            recv = ship(payload, perm)
            o_u = o_u.at[dst[off:off + g.rows]].set(recv)
            off += g.rows
    o = o_u[:slots].transpose(0, 2, 1, 3).reshape(tpw, hq, d)
    return o[None]


def fcp_attention(q, k, v, tables: dict[str, jax.Array], *,
                  spec: StaticSpec, mesh: jax.sharding.Mesh,
                  cp_axis: str = "data", head_axis: str | None = "model",
                  cfg: ExecConfig = ExecConfig(),
                  layout: str = "stream") -> jax.Array:
    """Distributed FCP attention.

    q: [F, tpw, HQ, D]; k/v: [F, tpw, KH, D]; ``F`` frames sharded over
    (pod?, data); heads sharded over ``head_axis``.  Returns o (f32) in
    the same layout — with the default ``layout="stream"`` the caller
    never sees the schedule layout (§4.3).  ``layout="sched"`` is the
    layer-pipelined contract: q/k/v arrive (and o returns) already in
    the schedule layout, the caller having moved the hidden state once
    per layer group with :func:`fcp_reshuffle`.
    """
    if layout not in ("stream", "sched"):
        raise ValueError(f"unknown layout {layout!r}")
    frame_axes = tuple(a for a in ("pod", cp_axis) if a in mesh.axis_names)
    dspec = P(frame_axes, None, head_axis, None)
    tspec = {k_: (P() if k_.startswith("blk_") else P(cp_axis))
             for k_ in tables}
    fn = functools.partial(_fcp_local, spec=spec, cp_axis=cp_axis, cfg=cfg,
                           layout=layout)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(dspec, dspec, dspec, tspec),
        out_specs=dspec, check_vma=False)(q, k, v, tables)


def _resh_local(x, t, *, spec: StaticSpec, cp_axis: str, reverse: bool):
    """Per-device hidden-state reshuffle: x [1, tpw, C] stream layout ->
    schedule layout (or back when ``reverse``)."""
    bs, slots = spec.block_size, spec.slots
    C = x.shape[-1]
    t = {k_: (v_ if k_.startswith("blk_") else v_[0])
         for k_, v_ in t.items()}
    # frame as [slots, 1, bs, C]: wire payloads are [rows, heads, blk,
    # dim], so the hidden state rides as a single fat "head".  Always
    # the f32 wire: the hidden state feeds every later layer — the
    # layer-pipelined path trades per-layer Q/K/V reshuffles for one
    # exact hidden-state move per group boundary.
    xf = x[0].reshape(slots, bs, C)[:, None]

    def ship(payload, perm):
        return wirelib.ship(payload, tuple(perm), cp_axis,
                            wirelib.WIRE_F32, _SCALE_AXES)

    def with_trash(y):
        return jnp.concatenate([y, jnp.zeros_like(y[:1])], axis=0)

    xt = with_trash(xf)
    src = t["restore_local_src"] if reverse else t["resh_local_src"]
    # mirrors _fcp_local: forward gathers local rows from the stream
    # frame; restore gathers from the trash-extended schedule frame
    # (restore_local_src may name the q-trash row)
    ys = with_trash(_gather_rows(xt if reverse else xf, src))
    for r in range(spec.n_resh_rounds):
        snd = (t["restore_send_slot"] if reverse
               else t["resh_send_slot"])[r]
        dst = (t["restore_dst_slot"] if reverse
               else t["resh_dst_slot"])[r]
        off = 0
        for g in spec.resh_rounds[r].groups:
            perm = (tuple((d_, s_) for s_, d_ in g.perm) if reverse
                    else g.perm)
            recv = ship(_gather_rows(xt, snd[off:off + g.rows]), perm)
            ys = ys.at[dst[off:off + g.rows]].set(recv)
            off += g.rows
    return ys[:slots, 0].reshape(1, slots * bs, C)


def fcp_reshuffle(x, tables: dict[str, jax.Array], *, spec: StaticSpec,
                  mesh: jax.sharding.Mesh, cp_axis: str = "data",
                  reverse: bool = False) -> jax.Array:
    """Move a per-token tensor between the stream and schedule layouts.

    x: [F, tpw, C] (any trailing channel count — hidden state, or
    hidden state with the rope positions concatenated as one extra f32
    channel).  Uses the schedule's reshuffle plan (``reverse=False``:
    stream -> schedule) or restore plan (``reverse=True``: schedule ->
    stream); payloads always travel the f32 wire (exact).  This is the
    layer-pipelined reshuffle primitive: move the hidden state once at
    a layer-group boundary, then run every layer of the group with
    :func:`fcp_attention` ``layout="sched"`` — per-layer Q/K/V
    reshuffles and O restores vanish (``docs/overlap.md``).
    """
    frame_axes = tuple(a for a in ("pod", cp_axis) if a in mesh.axis_names)
    dspec = P(frame_axes, None, None)
    tspec = {k_: (P() if k_.startswith("blk_") else P(cp_axis))
             for k_ in tables}
    fn = functools.partial(_resh_local, spec=spec, cp_axis=cp_axis,
                           reverse=reverse)
    return shard_map(fn, mesh=mesh, in_specs=(dspec, tspec),
                     out_specs=dspec, check_vma=False)(x, tables)


def schedule_tables(sched: Schedule) -> dict[str, jax.Array]:
    """Device tables for :func:`fcp_attention`.  All mask metadata
    (including for received blocks) is precomputed host-side into the
    step tables — only K/V bytes travel the network.

    Memoized on the schedule object: plan-cache hits (core/plan_cache.py)
    return the same ``Schedule``, so repeated batches reuse the uploaded
    tables (and the jit caches keyed on their shapes) instead of paying
    a fresh host->device transfer per step.
    """
    tables = getattr(sched, "_device_tables", None)
    if tables is None:
        tables = plan_tables(sched.arrays)
        sched._device_tables = tables
    return tables


def timed_call(fn, *args):
    """Health-telemetry timing hook for the host train loop:
    ``out, seconds = timed_call(jitted_step, *args)``.

    The wall clock is device-sync'd by blocking on the outputs *after*
    dispatch — nothing is added inside jit (zero recompiles, zero extra
    collectives), and a caller that would block on the outputs anyway
    (loss logging, checkpointing) pays nothing on the healthy path.
    Feeds :class:`repro.runtime.health.HealthMonitor.observe`.
    """
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# context-parallel decode (KV cache sharded along sequence)
# --------------------------------------------------------------------------

def _decode_local(q, kc, vc, lengths, *, seq_axes: tuple[str, ...],
                  axis_sizes: tuple[int, ...], shard_len: int,
                  cfg: ExecConfig):
    """q: [B_l, HQ_l, D] replicated over seq_axes; kc/vc: [B_l, S_l, KH, D];
    lengths: [B_l] valid cache lengths."""
    # global offset of this sequence shard
    off = jnp.int32(0)
    for ax, sz in zip(seq_axes, axis_sizes):
        off = off * sz + jax.lax.axis_index(ax)
    off = off * shard_len
    pos_k = off + jnp.arange(shard_len, dtype=jnp.int32)     # [S_l]

    # decode is single-partial per shard: the fused run impls degrade to
    # their per-step kernels here
    impl = {"fused": "pallas", "fused_xla": "xla"}.get(cfg.impl, cfg.impl)

    def one(qb, kb, vb, ln):
        seg_k = jnp.where(pos_k < ln, 0, -1).astype(jnp.int32)
        o, lse = ops.block_attention(
            qb[:, None], kb.transpose(1, 0, 2), vb.transpose(1, 0, 2),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            seg_k, pos_k, mask=False, impl=impl,
            block_q=cfg.block_q, block_k=cfg.block_k,
            interpret=cfg.interpret, xla_chunk=cfg.xla_chunk)
        return o[:, 0], lse[:, 0]                            # [HQ, D], [HQ]

    o, lse = jax.vmap(one)(q, kc, vc, lengths)
    # flash merge across sequence shards (numerically exact)
    m = lse
    for ax in seq_axes:
        m = jax.lax.pmax(m, ax)
    w = jnp.exp(lse - m)
    num = jax.lax.psum(o * w[..., None], seq_axes)
    den = jax.lax.psum(w, seq_axes)
    return num / jnp.maximum(den, 1e-37)[..., None]


def cp_cache_update(cache, new, pos, *, mesh: jax.sharding.Mesh,
                    batch_axis: str | None = "data",
                    seq_axes: Sequence[str] = ("model",),
                    head_axis: str | None = None):
    """Write one token into a sequence-sharded KV cache, collective-free.

    cache: [B, S, KH, D] with S sharded over ``seq_axes``; new: [B, KH, D];
    pos: [B].  Each shard masks the update to its own S range (the
    production pattern — a naive ``.at[pos].set`` on a sharded dim makes
    GSPMD all-gather the cache)."""
    seq_axes = tuple(seq_axes)
    axis_sizes = tuple(int(mesh.shape[a]) for a in seq_axes)
    n_shards = int(np.prod(axis_sizes))
    shard_len = cache.shape[1] // n_shards

    def local(cache, new, pos):
        off = jnp.int32(0)
        for ax, sz in zip(seq_axes, axis_sizes):
            off = off * sz + jax.lax.axis_index(ax)
        off = off * shard_len

        def one(c, n, p):
            lp = jnp.clip(p - off, 0, shard_len - 1)
            in_range = (p >= off) & (p < off + shard_len)
            # mask the UPDATE VALUE, not the buffer: a full-tensor
            # `where` would rewrite the whole cache shard every step
            # (measured 3.4 TB/step on qwen32b decode — §Perf C1)
            cur = jax.lax.dynamic_slice_in_dim(c, lp, 1, axis=0)
            val = jnp.where(in_range, n[None].astype(c.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(c, val, lp, axis=0)

        return jax.vmap(one)(cache, new, pos)

    cspec = P(batch_axis, seq_axes, head_axis, None)
    nspec = P(batch_axis, head_axis, None)
    return shard_map(local, mesh=mesh,
                         in_specs=(cspec, nspec, P(batch_axis)),
                         out_specs=cspec, check_vma=False)(cache, new, pos)


def cp_decode_attention(q, k_cache, v_cache, lengths, *,
                        mesh: jax.sharding.Mesh,
                        batch_axis: str | None = "data",
                        seq_axes: Sequence[str] = ("model",),
                        head_axis: str | None = None,
                        cfg: ExecConfig = ExecConfig()) -> jax.Array:
    """One-token decode against a sequence-sharded KV cache.

    q: [B, HQ, D]; k/v_cache: [B, S, KH, D]; lengths: [B].
    The cache's S dim is sharded over ``seq_axes``; per-shard partial
    attentions merge with a pmax/psum flash reduction.
    """
    seq_axes = tuple(seq_axes)
    axis_sizes = tuple(int(mesh.shape[a]) for a in seq_axes)
    n_shards = int(np.prod(axis_sizes))
    shard_len = k_cache.shape[1] // n_shards
    qspec = P(batch_axis, head_axis, None)
    cspec = P(batch_axis, seq_axes, head_axis, None)
    lspec = P(batch_axis)
    fn = functools.partial(_decode_local, seq_axes=seq_axes,
                           axis_sizes=axis_sizes, shard_len=shard_len,
                           cfg=cfg)
    return shard_map(
        fn, mesh=mesh, in_specs=(qspec, cspec, cspec, lspec),
        out_specs=qspec, check_vma=False)(q, k_cache, v_cache, lengths)
