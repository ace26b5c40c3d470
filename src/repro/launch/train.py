"""Training step assembly + CLI driver.

``build_train_step`` wires: FCP schedule -> distributed attention closure
-> model loss -> grads (+ optional error-feedback bf16 DP compression) ->
AdamW, all under one jit with NamedSharding in/out (FSDP over data, TP
over model, DP over pod) and donated state.

CLI:  PYTHONPATH=src python -m repro.launch.train --arch stablelm_1_6b \
          --shape train_4k --steps 20 --mesh 4x2 --dist real_world
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..checkpoint.manager import CheckpointManager
from ..configs.base import (ModelConfig, ParallelConfig, TrainConfig,
                            apply_overrides, get_config, smoke_config)
from ..core import executor as ex
from ..core import plan_cache as pc
from ..core.schedule import Schedule, make_schedule
from ..data.loader import Batch, LoaderState, SyntheticLoader
from ..masks import MaskSpec, coerce_mask, parse_mask
from ..models import Model
from ..optimizer import adamw, schedules
from ..parallel import sharding as sh
from ..runtime import compression
from ..runtime import elastic
from ..runtime import health as health_mod
from .compile_cache import enable_compile_cache


def make_fcp_attn_fn(sched: Schedule, mesh, pcfg: ParallelConfig
                     ) -> Callable:
    tables = ex.schedule_tables(sched)
    cfg_exec = ex.ExecConfig(
        impl=pcfg.attention_impl,
        block_q=pcfg.attn_block_q, block_k=pcfg.attn_block_k,
        interpret=pcfg.attn_interpret,
        out_dtype="bfloat16" if pcfg.attn_out_bf16 else None)
    head_axis = pcfg.tp_axis if pcfg.tp_axis in mesh.axis_names else None

    def attn(q, k, v):
        return ex.fcp_attention(q, k, v, tables, spec=sched.spec, mesh=mesh,
                                cp_axis=pcfg.cp_axis, head_axis=head_axis,
                                cfg=cfg_exec)
    return attn


@dataclasses.dataclass
class PipelinedAttn:
    """One per-layer entry of the layer-pipelined reshuffle
    (``docs/overlap.md``; consumed duck-typed by
    :func:`repro.models.transformer.forward`).

    ``attn`` runs FCP attention with ``layout="sched"`` (no per-layer
    Q/K/V reshuffle or O restore); ``enter``/``exit`` — set only on the
    first/last layer of a same-mask layer group — move the hidden state
    (and rope positions) between the stream and schedule layouts via
    :func:`repro.core.executor.fcp_reshuffle`."""
    attn: Callable
    enter: Callable | None = None
    exit: Callable | None = None


def make_pipelined_attn_fns(cfg: ModelConfig, pcfg: ParallelConfig,
                            layer_masks, scheds, mesh) -> tuple:
    """Per-layer :class:`PipelinedAttn` entries: the hidden state stays
    resident in the schedule layout across each run of consecutive
    same-mask layers and moves once per group boundary, so N layers pay
    one reshuffle + one restore instead of N of each.  Positions ride
    the move as one extra f32 channel (token positions are < 2**24, so
    the f32 wire carries them exactly).  Model-level transform only —
    schedules and plan keys are those of the non-pipelined run."""
    if cfg.family not in ("dense", "moe", "audio", "vlm"):
        raise ValueError(
            f"layer_pipeline is not supported for family "
            f"{cfg.family!r} (shared/absent attention)")
    cfg_exec = ex.ExecConfig(
        impl=pcfg.attention_impl,
        block_q=pcfg.attn_block_q, block_k=pcfg.attn_block_k,
        interpret=pcfg.attn_interpret,
        out_dtype="bfloat16" if pcfg.attn_out_bf16 else None)
    head_axis = pcfg.tp_axis if pcfg.tp_axis in mesh.axis_names else None

    def group_fns(m):
        sched = scheds[m]
        tables, spec = ex.schedule_tables(sched), sched.spec

        def attn(q, k, v):
            return ex.fcp_attention(
                q, k, v, tables, spec=spec, mesh=mesh,
                cp_axis=pcfg.cp_axis, head_axis=head_axis, cfg=cfg_exec,
                layout="sched")

        def enter(x, pos):
            xp = jnp.concatenate(
                [x.astype(jnp.float32),
                 pos.astype(jnp.float32)[..., None]], axis=-1)
            xp = ex.fcp_reshuffle(xp, tables, spec=spec, mesh=mesh,
                                  cp_axis=pcfg.cp_axis)
            # exact round trips: bf16 values survive the f32 wire, and
            # integer positions recover via round
            return (xp[..., :-1].astype(x.dtype),
                    jnp.round(xp[..., -1]).astype(pos.dtype))

        def exit_(x):
            y = ex.fcp_reshuffle(x.astype(jnp.float32), tables,
                                 spec=spec, mesh=mesh,
                                 cp_axis=pcfg.cp_axis, reverse=True)
            return y.astype(x.dtype)

        return attn, enter, exit_

    by_mask = {m: group_fns(m) for m in dict.fromkeys(layer_masks)}
    n = len(layer_masks)
    entries = []
    for i, m in enumerate(layer_masks):
        attn, enter, exit_ = by_mask[m]
        first = i == 0 or layer_masks[i - 1] != m
        last = i == n - 1 or layer_masks[i + 1] != m
        entries.append(PipelinedAttn(attn=attn,
                                     enter=enter if first else None,
                                     exit=exit_ if last else None))
    return tuple(entries)


def layer_mask_specs(cfg: ModelConfig, pcfg: ParallelConfig
                     ) -> tuple[MaskSpec, ...]:
    """Per-layer mask family: the model config's ``attn_mask_pattern``
    (cycled over the stack) when present, else the run-wide
    ``ParallelConfig.attn_mask`` for every layer."""
    n = max(cfg.n_layers, 1)
    if getattr(cfg, "attn_mask_pattern", ()):
        pat = [parse_mask(str(s)) for s in cfg.attn_mask_pattern]
        return tuple(pat[i % len(pat)] for i in range(n))
    return (coerce_mask(pcfg.attn_mask),) * n


def _param_dtype_bytes(cfg: ModelConfig) -> int:
    """Itemsize of the compute dtype the executor's payloads ship in
    (q/k/v inherit ``param_dtype``) — prices the wire in real bytes:
    under bf16 training the bf16 wire is a no-op, int8 still halves.
    The driver folds this into ``ParallelConfig.in_dtype_bytes`` so
    elastic replans reprice identically."""
    return int(jnp.dtype(cfg.param_dtype).itemsize)


def build_schedule(cfg: ModelConfig, pcfg: ParallelConfig, seqlens,
                   n_cp: int, tokens_per_worker: int,
                   speeds: np.ndarray | None = None,
                   mask=True, verify: bool | None = None) -> Schedule:
    tp = 1  # schedule is head-count agnostic (costs scale uniformly)
    nh, nkv = cfg.padded_heads(tp)
    return make_schedule(
        seqlens, n_cp, tokens_per_worker, pcfg.block_size,
        n_q_heads=max(nh, 1), n_kv_heads=max(nkv, 1),
        head_dim=max(cfg.head_dim, 1), mask=mask, speeds=speeds,
        coalesce=pcfg.coalesce, wire=pcfg.comm_dtype,
        in_dtype_bytes=pcfg.in_dtype_bytes, overlap=pcfg.overlap,
        locality={"auto": "auto", "on": True, "off": False}.get(
            str(pcfg.locality), pcfg.locality),
        verify=verify)


def schedule_plan_key(cfg: ModelConfig, pcfg: ParallelConfig, seqlens,
                      n_cp: int, tokens_per_worker: int,
                      speeds: np.ndarray | None = None,
                      mask=True) -> tuple:
    """Plan-cache key matching :func:`build_schedule`'s determinism."""
    nh, nkv = cfg.padded_heads(1)
    return pc.plan_key(
        seqlens, n_cp, tokens_per_worker, pcfg.block_size,
        mask=mask, coalesce=pcfg.coalesce, locality=pcfg.locality,
        speeds=speeds, wire=pcfg.comm_dtype,
        in_dtype_bytes=pcfg.in_dtype_bytes, overlap=pcfg.overlap,
        extra=(max(nh, 1), max(nkv, 1), max(cfg.head_dim, 1)))


@dataclasses.dataclass
class TrainState:
    params: dict
    opt: adamw.AdamWState
    residual: dict | None = None           # grad-compression feedback

    def tree(self):
        t = {"params": self.params, "opt": self.opt}
        if self.residual is not None:
            t["residual"] = self.residual
        return t


def build_train_step(model: Model, mesh, pcfg: ParallelConfig,
                     tcfg: TrainConfig, attn_fn: Callable | None):
    def train_step(params, opt, residual, batch):
        lr = schedules.warmup_cosine(
            opt.step, peak_lr=tcfg.lr, warmup_steps=tcfg.warmup_steps,
            total_steps=tcfg.total_steps)

        remat = pcfg.remat_policy if pcfg.remat else False

        def loss_fn(p):
            return model.loss(p, batch, attn_fn, remat=remat,
                              chunked=pcfg.chunked_loss)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        if tcfg.grad_compression:
            # bf16 error-feedback compression of the cross-pod (DCN)
            # gradient reduction (runtime/compression.py)
            grads, residual = compression.compress_grads(grads, residual)
            grads = compression.decompress_grads(grads)
        params, opt, gnorm = adamw.update(
            params, grads, opt, lr=lr, b1=tcfg.b1, b2=tcfg.b2,
            weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip)
        return params, opt, residual, loss, gnorm

    return train_step


def _state_shardings(params_like, opt_like, mesh, fsdp: bool = True):
    psh = sh.param_shardings(params_like, mesh, fsdp=fsdp)
    osh = adamw.AdamWState(
        step=NamedSharding(mesh, P()),
        m=sh.param_shardings(opt_like.m, mesh, fsdp=fsdp),
        v=sh.param_shardings(opt_like.v, mesh, fsdp=fsdp))
    return psh, osh


def init_state(model: Model, mesh, seed: int):
    """Parameters and AdamW state, created in their train shardings so
    no device ever holds the whole unsharded model (on one chip the
    full ``stablelm_1_6b`` state alone exceeds a v5e's HBM)."""
    key = jax.random.key(seed)
    p_like = jax.eval_shape(model.init, key)
    o_like = jax.eval_shape(adamw.init, p_like)
    psh, osh = _state_shardings(p_like, o_like, mesh)
    params = jax.jit(model.init, out_shardings=psh)(key)
    return params, jax.jit(adamw.init, out_shardings=osh)(params)


def jit_train_step(train_step, mesh, params_like, opt_like, residual_like,
                   batch_like, fsdp: bool = True):
    psh, osh = _state_shardings(params_like, opt_like, mesh, fsdp)
    rsh = (sh.param_shardings(residual_like, mesh, fsdp=fsdp)
           if residual_like is not None else None)
    bsh = sh.batch_shardings(batch_like, mesh)
    rep = NamedSharding(mesh, P())
    return jax.jit(train_step,
                   in_shardings=(psh, osh, rsh, bsh),
                   out_shardings=(psh, osh, rsh, rep, rep),
                   donate_argnums=(0, 1, 2))


def batch_arrays(b: Batch, cfg: ModelConfig, rng=None) -> dict:
    out = {
        "tokens": jnp.asarray(b.tokens),
        "labels": jnp.asarray(b.labels),
        "positions": jnp.asarray(b.positions),
        "loss_mask": jnp.asarray(b.loss_mask),
    }
    if cfg.frontend_dim:
        f, t = b.tokens.shape
        rng = rng or np.random.default_rng(0)
        # frontend stub: first n_fe positions of each frame are "patches"
        n_fe = min(256, t)
        fe = rng.normal(size=(f, n_fe, cfg.frontend_dim)) * 0.02
        mask = np.zeros((f, t), bool)
        mask[:, :n_fe] = True
        out["frontend_embeds"] = jnp.asarray(fe, jnp.float32)
        out["frontend_mask"] = jnp.asarray(mask)
        # no next-token loss on patch positions
        out["loss_mask"] = out["loss_mask"] * (1.0 - mask.astype(np.float32))
    return out


def route_layers(cfg: ModelConfig, layer_masks, group_masks, fn_of_mask):
    """One shared attention closure when the model is mask-uniform, else
    the per-layer sequence the model unrolls over (per-layer-group
    scheduling)."""
    if len(group_masks) == 1:
        return fn_of_mask(group_masks[0])
    if cfg.family not in ("dense", "moe", "audio", "vlm"):
        raise ValueError(
            f"per-layer attention-mask patterns are not supported for "
            f"family {cfg.family!r} (shared/absent attention)")
    by_mask = {m: fn_of_mask(m) for m in group_masks}
    return tuple(by_mask[m] for m in layer_masks)


# --------------------------------------------------------------------------
# fault-tolerant supervised loop (runtime health closed loop)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class StepRecord:
    """One committed step, for drills/benches to diff against."""
    step: int
    loss: float
    gnorm: float
    n_workers: int
    ms: float
    pods: int = 1


class Supervisor:
    """Fault-tolerant elastic FCP training driver.

    Owns model/optimizer state, a *geometry-pinned* data loader, a
    :class:`~repro.runtime.health.HealthMonitor`, a shared plan cache
    (+ plan-ahead thread), and an optional checkpoint manager, and
    closes the measurement -> placement -> recovery loop around the
    jitted train step:

    * **healthy path** — identical work to the plain CLI loop (plan
      cache, plan-ahead, bounded compiled-step cache) plus one
      device-sync'd wall clock per step (``executor.timed_call`` — the
      loop blocks on the loss anyway).  The monitor's planning speeds
      stay ``None`` while healthy, so plan-cache keys are byte-identical
      to a monitor-less run: zero added recompiles.
    * **straggler path** — when the monitor's hysteresis window fills,
      its latched quantized speeds flow into cache-backed
      ``elastic.replan(speeds=...)`` so the chronically slow worker is
      assigned proportionally fewer (or cheaper) blocks; demote/promote
      events are rate-limited (``demote_cooldown``) and logged.
    * **loss path** — on :class:`~repro.runtime.health.WorkerLoss`,
      :class:`~repro.runtime.health.PodLoss` or
      :class:`~repro.runtime.elastic.InjectedFailure` the fleet shrinks
      to the survivors (new mesh, ``elastic.replan`` on the survivor
      set), error-feedback residuals reset (never silently reused
      across a topology change), the newest *intact* committed
      checkpoint restores, and the deterministic data stream replays —
      losing at most ``checkpoint_every`` steps.  A *pod* loss shrinks
      the pod dimension to the largest divisor of the pinned pod count
      (schedule tables replicate over pods, so every surviving pod must
      see the same composition; a non-divisor remainder idles) and
      kicks off **overlapping recovery**: survivors continue training
      immediately while a background thread pre-warms the regrow path —
      prefetching the pre-shrink plan-cache keys via
      ``elastic.replan_key``, statically verifying the survivor
      schedules, and staging the newest committed checkpoint in host
      memory — so a returning pod rejoins at a step boundary
      (``run(rejoin_step=...)``) with a measured, gated cost instead of
      a cold restart.  See ``docs/elasticity.md``.

    The loader is pinned to the *original* ``pods x n_workers x
    tokens_per_worker`` geometry no matter the current fleet: the
    global token stream is a pure function of ``(seed, step)`` and must
    not change shape under elasticity, so survivor fleets view the same
    stream through ``elastic.reshape_pod_frames`` (each surviving pod
    adopts whole pinned-pod sub-streams; padding is re-derived for the
    replanned frame geometry).
    """

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig,
                 tcfg: TrainConfig, *, n_workers: int,
                 tokens_per_worker: int, pods: int = 1,
                 dist: str = "uniform",
                 uniform_len: int = 1024, fresh: bool = False,
                 checkpoint_dir=None, checkpoint_keep: int = 3,
                 monitor: "health_mod.HealthMonitor | None" = None,
                 start_fleet=None, verbose: bool = True):
        self.cfg, self.pcfg, self.tcfg = cfg, pcfg, tcfg
        self.p0 = int(pods)
        self.n0 = int(n_workers)
        self.tpw0 = int(tokens_per_worker)
        # start_fleet: None = full strength, (pods, workers) tuple, or a
        # bare worker count (legacy single-pod callers)
        if start_fleet is None:
            self.pods, self.n = self.p0, self.n0
        elif isinstance(start_fleet, tuple):
            self.pods, self.n = int(start_fleet[0]), int(start_fleet[1])
        else:
            self.pods, self.n = self.p0, int(start_fleet)
        self.verbose = verbose
        if not (cfg.uses_attention and cfg.n_layers):
            raise ValueError("Supervisor drives FCP attention models")
        self.model = Model(cfg, tp=1)
        self.loader = SyntheticLoader(
            dist=dist, n_frames=self.n0, tokens_per_worker=self.tpw0,
            vocab_size=cfg.vocab_size, pods=self.p0, seed=tcfg.seed,
            uniform_len=uniform_len, plan_buckets=pcfg.plan_buckets,
            bucket_min_len=pcfg.block_size, fresh=fresh)
        self.monitor = monitor or health_mod.HealthMonitor.from_pcfg(
            self.pods * self.n, pcfg,
            topology=health_mod.FleetTopology(self.pods, self.n))
        self.plan_cache = pc.PlanCache(pcfg.plan_cache_size)
        self.planner = pc.PlanAheadPlanner(self.plan_cache,
                                           enabled=pcfg.plan_ahead)
        # checkpoint_keep: GC window.  Drills that replay a recovery
        # against a pruned copy of the directory widen it so the
        # resume-step checkpoint survives to the end of the run.
        self.manager = (CheckpointManager(checkpoint_dir,
                                          keep_n=checkpoint_keep)
                        if checkpoint_dir else None)
        self._meshes: dict = {}
        self.params, self.opt = init_state(
            self.model, self._mesh(self.pods, self.n), tcfg.seed)
        self.residual = (compression.init_residuals(self.params)
                         if tcfg.grad_compression else None)
        # host copies of step 0 (np.array forces real copies — the live
        # jax buffers are donated every step): checkpointless recovery
        # falls back to replaying from scratch
        self._init_tree = jax.tree.map(
            lambda x: np.array(x),
            {"params": self.params, "opt": self.opt})
        self.layer_masks = layer_mask_specs(cfg, pcfg)
        self.group_masks = list(dict.fromkeys(self.layer_masks))
        nh, nkv = cfg.padded_heads(1)
        self._heads = (max(nh, 1), max(nkv, 1), max(cfg.head_dim, 1))
        self._step_cache: dict = {}
        self.compiled_at: list[int] = []     # steps that built a new jit
        self.history: list[StepRecord] = []
        self.recoveries: list[dict] = []
        self.rejoins: list[dict] = []
        self.last_scheds: dict = {}
        self._prewarm = None                 # regrow-prewarm thread
        self._prewarm_info: dict | None = None
        self._staged: dict | None = None     # host-staged checkpoint

    # -- geometry ----------------------------------------------------------

    def _mesh(self, pods: int, n: int):
        ck = (pods, n)
        if ck not in self._meshes:
            from .mesh import make_mesh
            if self.p0 == 1 and pods == 1:
                self._meshes[ck] = make_mesh((n, 1), ("data", "model"))
            else:
                # pod axis stays first-class even at pods == 1 so a
                # shrunken multi-pod fleet keeps one mesh family (and
                # the reference drill run matches it bit-for-bit)
                self._meshes[ck] = make_mesh(
                    (pods, n, 1), ("pod", "data", "model"))
        return self._meshes[ck]

    def _fleet_batch(self, b: Batch, pods: int, n: int, tpw: int) -> Batch:
        """Re-view the pinned-geometry batch on the current fleet:
        same global token stream, each surviving pod adopting
        ``p0 // pods`` pinned-pod sub-streams, padding re-derived
        (segment ids pad with -1 so padding never aliases a document)."""
        n_valid = int(sum(b.seqlens))

        def rs(a, fill=0):
            return elastic.reshape_pod_frames(a, self.p0, pods, n, tpw,
                                              n_valid=n_valid, fill=fill)
        return Batch(tokens=rs(b.tokens), labels=rs(b.labels),
                     positions=rs(b.positions),
                     seg_ids=rs(b.seg_ids, fill=-1),
                     loss_mask=rs(b.loss_mask),
                     seqlens=elastic.pod_survivor_seqlens(
                         b.seqlens, self.p0, pods),
                     composition_id=b.composition_id)

    # -- planning ----------------------------------------------------------

    def _group_key(self, seqlens, pods: int, n: int, m, speeds) -> tuple:
        return elastic.replan_key(seqlens, n, self.pcfg.block_size,
                                  mask=m, speeds=speeds, pcfg=self.pcfg,
                                  pods=pods, base_pods=self.p0)

    def _group_build(self, seqlens, pods: int, n: int, m, speeds):
        nh, nkv, hd = self._heads
        return functools.partial(
            elastic.replan, seqlens, n, self.pcfg.block_size,
            n_q_heads=nh, n_kv_heads=nkv, head_dim=hd, mask=m,
            speeds=None if speeds is None else np.asarray(speeds),
            pcfg=self.pcfg, verify=None, pods=pods, base_pods=self.p0)

    def _plan(self, seqlens, pods: int, n: int, speeds):
        """One cache-backed survivor replan per distinct mask group,
        under the exact keys ``elastic.replan`` uses — a re-grown fleet
        re-hits its pre-shrink plans."""
        scheds: dict[MaskSpec, Schedule] = {}
        keys = []
        for m in self.group_masks:
            key = self._group_key(seqlens, pods, n, m, speeds)
            scheds[m] = self.planner.get(
                key, self._group_build(seqlens, pods, n, m, speeds))
            keys.append(key)
        return scheds, tuple(keys)

    def _prefetch(self, seqlens, pods: int, n: int, speeds) -> None:
        for m in self.group_masks:
            self.planner.prefetch(
                self._group_key(seqlens, pods, n, m, speeds),
                self._group_build(seqlens, pods, n, m, speeds))

    def _step_fn(self, step: int, pods: int, n: int, keys: tuple,
                 scheds, batch):
        ck = (pods, n, keys)
        if ck not in self._step_cache:
            mesh = self._mesh(pods, n)
            if self.pcfg.layer_pipeline:
                attn = make_pipelined_attn_fns(
                    self.cfg, self.pcfg, self.layer_masks, scheds, mesh)
            else:
                attn = route_layers(
                    self.cfg, self.layer_masks, self.group_masks,
                    lambda m: make_fcp_attn_fn(scheds[m], mesh, self.pcfg))
            ts = build_train_step(self.model, mesh, self.pcfg,
                                  self.tcfg, attn)
            self._step_cache[ck] = jit_train_step(
                ts, mesh, self.params, self.opt, self.residual, batch)
            self.compiled_at.append(step)
            while len(self._step_cache) > max(self.pcfg.plan_cache_size,
                                              1):
                self._step_cache.pop(next(iter(self._step_cache)))
        return self._step_cache[ck]

    # -- checkpointing -----------------------------------------------------

    def _save(self, step: int) -> None:
        if self.manager is None:
            return
        self.manager.save(
            step, {"params": self.params, "opt": self.opt},
            extra={"loader": self.loader.state.to_dict(),
                   "n_workers": self.n, "pods": self.pods},
            blocking=False)

    def _restore(self) -> int:
        """Roll state back to the newest committed checkpoint (or step 0
        from the held initial copies) and return the resume step.  The
        loader state rewinds with the weights, so the replayed stream
        is bit-identical to the first pass (pure in ``(seed, step)``)."""
        if self.manager is not None and self.manager.latest_step() is not None:
            tree, extra = self.manager.restore(
                {"params": self.params, "opt": self.opt})
            self.params = jax.tree.map(jnp.asarray, tree["params"])
            self.opt = jax.tree.map(jnp.asarray, tree["opt"])
            self.loader.state = LoaderState.from_dict(extra["loader"])
            return int(extra["step"]) + 1
        self.params = jax.tree.map(jnp.asarray, self._init_tree["params"])
        self.opt = jax.tree.map(jnp.asarray, self._init_tree["opt"])
        self.loader.state = LoaderState(step=0, seed=self.tcfg.seed)
        return 0

    # -- driver ------------------------------------------------------------

    def run(self, total_steps: int, *, fail=None, skew=None,
            rejoin_step: int | None = None) -> dict:
        """Train to ``total_steps``, surviving worker and pod loss.

        ``fail`` (an :class:`~repro.runtime.elastic.InjectedFailure`
        with ``worker=``/``pod=`` plus ``step``/``round`` set) kills
        that worker — or that whole pod — mid-step once; ``skew`` maps
        flat worker id -> slowdown factor for the telemetry (sim
        stand-in for a degraded chip); ``rejoin_step`` regrows a
        shrunken fleet back to full strength at that step boundary
        (sim stand-in for the lost pod returning).  Auto-resumes from
        the newest intact committed checkpoint when one exists."""
        step = 0
        if self.manager is not None and self.manager.latest_step() is not None:
            step = self._restore()
        while step < total_steps:
            try:
                step = self._run_steps(step, total_steps, fail, skew,
                                       rejoin_step)
            except (health_mod.WorkerLoss, health_mod.PodLoss,
                    elastic.InjectedFailure) as e:
                t0 = time.perf_counter()
                at = int(getattr(e, "step", None) or step)
                pod = getattr(e, "pod", None)
                rec: dict = {"failed_step": at}
                if pod is not None:
                    if self.pods <= 1:
                        raise
                    lost = int(pod) % self.pods
                    # schedule tables replicate over the pod axis, so
                    # every surviving pod must view the same pinned
                    # compositions: demote to the largest divisor fleet
                    # and idle the remainder (docs/elasticity.md)
                    new_pods = max(d for d in range(1, self.pods)
                                   if self.p0 % d == 0)
                    if isinstance(e, elastic.InjectedFailure):
                        self.monitor.note_failure(
                            at, pod=lost,
                            detail=f"injected at round {e.round}")
                    self.monitor.resize(
                        topology=health_mod.FleetTopology(new_pods,
                                                          self.n))
                    rec["pod"] = lost
                    rec["idle_pods"] = (self.pods - 1) - new_pods
                    self.pods = new_pods
                    what = f"pod {lost}"
                else:
                    lost = (int(getattr(e, "worker", None) or 0)
                            % (self.pods * self.n))
                    if isinstance(e, elastic.InjectedFailure):
                        self.monitor.note_failure(
                            at, lost, detail=f"injected at round {e.round}")
                    if self.pods == 1:
                        survivors = [i for i in range(self.n) if i != lost]
                        if not survivors:
                            raise
                        self.monitor.resize(survivors)
                        self.n = len(survivors)
                    else:
                        # multi-pod worker loss: pods run one replicated
                        # schedule, so the lost worker's slot demotes
                        # fleet-wide (uniform per-pod worker count)
                        if self.n <= 1:
                            raise
                        self.monitor.resize(
                            topology=health_mod.FleetTopology(
                                self.pods, self.n - 1))
                        self.n -= 1
                    rec["worker"] = lost
                    what = f"worker {lost}"
                if self.residual is not None:
                    # EF residuals accumulate per-topology quantization
                    # error — never reuse them across a resize
                    self.residual = compression.init_residuals(self.params)
                    rec["ef_reset"] = True
                resume = self._restore()
                rec.update(resume_step=resume, steps_lost=at - resume,
                           pods=self.pods, n_workers=self.n,
                           wall_s=time.perf_counter() - t0)
                self.recoveries.append(rec)
                if pod is not None:
                    # overlapping recovery: survivors train on while the
                    # regrow path warms in the background
                    self._start_prewarm(resume)
                if self.verbose:
                    print(f"[supervisor] lost {what} ({e}); replanning "
                          f"on {self.pods}x{self.n} survivors, resuming "
                          f"at step {resume}", flush=True)
                step = resume
                fail = None                  # consumed
        if self._prewarm is not None:
            self._prewarm.join()
            self._prewarm = None
        self.planner.shutdown()
        if self.manager is not None:
            self.manager.wait()
        return self.summary()

    def _run_steps(self, start: int, total: int, fail, skew,
                   rejoin_step=None) -> int:
        for step in range(start, total):
            if (rejoin_step is not None and step >= int(rejoin_step)
                    and (self.pods, self.n) != (self.p0, self.n0)):
                self._rejoin(step)
            pods, n = self.pods, self.n
            nt = pods * n
            skew_vec = None
            if skew:
                skew_vec = np.ones(nt)
                for w, f in dict(skew).items():
                    if 0 <= int(w) < nt:
                        skew_vec[int(w)] = float(f)
            b = self.loader.next()
            if fail is not None and step == int(fail.step):
                hit = (int(fail.pod) < pods if fail.pod is not None
                       else int(fail.worker) < nt)
                if hit:
                    # mid-step: the batch was fetched and the round loop
                    # "started" — the step never commits, and the loader
                    # state is intentionally left advanced; recovery
                    # must rewind it from the checkpoint (replay proof)
                    raise fail
            speeds = self.monitor.planning_speeds()
            scheds, keys = self._plan(b.seqlens, pods, n, speeds)
            tpw = elastic.replan_tpw(
                elastic.pod_survivor_seqlens(b.seqlens, self.p0, pods),
                n, self.pcfg.block_size)
            batch = batch_arrays(self._fleet_batch(b, pods, n, tpw),
                                 self.cfg)
            fn = self._step_fn(step, pods, n, keys, scheds, batch)
            if step + 1 < total:
                self._prefetch(self.loader.peek_seqlens(), pods, n,
                               speeds)
            out, dt = ex.timed_call(fn, self.params, self.opt,
                                    self.residual, batch)
            self.params, self.opt, self.residual, loss, gnorm = out
            self.monitor.observe(
                step, health_mod.per_worker_times(dt, nt, skew_vec))
            ev = self.monitor.maybe_replan(step)
            if ev is not None and self.verbose:
                print(f"[supervisor] {ev.kind} workers {ev.workers} "
                      f"at step {step} (speeds {ev.speeds}): "
                      f"{ev.detail}", flush=True)
            self.monitor.check(step)
            self.history.append(StepRecord(step, float(loss),
                                           float(gnorm), n, dt * 1e3,
                                           pods))
            self.last_scheds = scheds
            every = max(int(self.pcfg.checkpoint_every), 0)
            if every and (step + 1) % every == 0:
                self._save(step)
            if self.verbose:
                print(f"step {step:5d}  loss {float(loss):.4f}  "
                      f"gnorm {float(gnorm):.3f}  "
                      f"[{pods}x{n}w {dt * 1e3:.0f}ms]", flush=True)
        return total

    # -- overlapping recovery ----------------------------------------------

    def _start_prewarm(self, resume: int) -> None:
        """Spawn the regrow-prewarm thread after a pod loss: it builds
        and verifies survivor plans, re-warms the full-fleet plan-cache
        keys the regrown fleet will ask for, and stages the newest
        committed checkpoint in host memory — all while survivors keep
        training (plan cache and planner are thread-safe)."""
        import threading
        self._prewarm_info = {
            "plans_prefetched": 0, "survivor_schedules_verified": 0,
            "violations": 0, "staged_step": None}
        # pin the checkpoint to stage *now* (the newest committed step
        # the recovery itself restored) — survivor saves landing while
        # the thread runs must not move the staging target
        stage = (self.manager.latest_step()
                 if self.manager is not None else None)
        self._prewarm = threading.Thread(
            target=self._prewarm_regrow,
            args=(resume, stage, self._prewarm_info), daemon=True)
        self._prewarm.start()

    def _prewarm_regrow(self, resume: int, stage: int | None,
                        info: dict) -> None:
        from ..analysis import verifier
        from ..checkpoint import checkpointer
        try:
            # the pinned stream's distinct upcoming compositions (pure
            # in (seed, step): safe to peek from a thread)
            horizon = (max(2 * self.monitor.window, 4) if self.loader.fresh
                       else len(self.loader.compositions))
            seen: set = set()
            comps = []
            for k in range(horizon):
                cid, seqlens = self.loader.composition(resume + k)
                if cid not in seen:
                    seen.add(cid)
                    comps.append(seqlens)
            nh, nkv, hd = self._heads
            for seqlens in comps:
                for m in self.group_masks:
                    # survivor-fleet plan: build (shared with the live
                    # loop via get_or_build) and statically verify
                    skey = self._group_key(seqlens, self.pods, self.n,
                                           m, None)
                    sched = self.plan_cache.get_or_build(
                        skey, self._group_build(seqlens, self.pods,
                                                self.n, m, None))
                    bad = verifier.verify_schedule(
                        sched, n_q_heads=nh, n_kv_heads=nkv, head_dim=hd,
                        in_dtype_bytes=float(self.pcfg.in_dtype_bytes))
                    info["survivor_schedules_verified"] += 1
                    info["violations"] += len(bad)
                    # full-fleet plan the regrown fleet will need — at
                    # full strength replan_key reduces to the pre-shrink
                    # key, so warmup plans re-hit here
                    fkey = self._group_key(seqlens, self.p0, self.n0,
                                           m, None)
                    if fkey not in self.plan_cache:
                        self.plan_cache.get_or_build(
                            fkey, self._group_build(seqlens, self.p0,
                                                    self.n0, m, None))
                    info["plans_prefetched"] += 1
            if self.manager is not None and stage is not None:
                like = {"params": self._init_tree["params"],
                        "opt": self._init_tree["opt"]}
                tree = checkpointer.restore(self.manager.path(stage),
                                            like)
                self._staged = {"step": int(stage), "tree": tree}
                info["staged_step"] = int(stage)
        except Exception as exc:    # best-effort: rejoin falls back cold
            info["error"] = repr(exc)

    def _rejoin(self, step: int) -> None:
        """Regrow the fleet to full strength at a step boundary: join
        the prewarm thread, reset monitor topology (with recalibration
        burn-in) and EF residuals, and record the measured rejoin cost
        plus whether the full-fleet plan keys were already cached."""
        t0 = time.perf_counter()
        if self._prewarm is not None:
            self._prewarm.join()
            self._prewarm = None
        m0 = self.plan_cache.stats.misses
        c0 = len(self.compiled_at)
        self.pods, self.n = self.p0, self.n0
        self.monitor.resize(
            topology=health_mod.FleetTopology(self.p0, self.n0))
        # the regrown fleet adopts the survivors' live state: pull it to
        # host and rebind as uncommitted arrays so the full-fleet jit
        # re-shards onto the big mesh (the broadcast a real rejoin pays
        # — measured as part of rejoin_ms)
        self.params = jax.tree.map(
            lambda x: jnp.asarray(np.asarray(x)), self.params)
        self.opt = jax.tree.map(
            lambda x: jnp.asarray(np.asarray(x)), self.opt)
        if self.residual is not None:
            self.residual = compression.init_residuals(self.params)
        keys_cached = all(
            self._group_key(self.loader.peek_seqlens(), self.p0,
                            self.n0, m, None) in self.plan_cache
            for m in self.group_masks)
        self.rejoins.append({
            "step": step, "pods": self.pods, "n_workers": self.n,
            "rejoin_ms": (time.perf_counter() - t0) * 1e3,
            "plan_keys_cached": keys_cached,
            "plan_misses_before": m0, "compiles_before": c0,
            "prewarm": self._prewarm_info})
        if self.verbose:
            print(f"[supervisor] pod rejoin at step {step}: fleet back "
                  f"to {self.p0}x{self.n0} "
                  f"({'warm' if keys_cached else 'cold'} plans)",
                  flush=True)

    def summary(self) -> dict:
        s = self.plan_cache.stats
        return {
            "steps": len(self.history),
            "pods": self.pods,
            "n_workers": self.n,
            "recoveries": self.recoveries,
            "rejoins": self.rejoins,
            "events": [dataclasses.asdict(e)
                       for e in self.monitor.events],
            "compiles": len(self.compiled_at),
            "plan_cache": s.to_dict(),
            "plan_ahead_hits": self.planner.prefetched_hits,
        }


# --------------------------------------------------------------------------
# CLI driver
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TrainResult:
    """What :func:`main` ran: per-step losses, the live state, and (plain
    loop only) the last jitted step with the batch it took, so a caller
    can inspect the compiled program."""
    losses: list[float]
    params: dict
    opt: adamw.AdamWState
    plan_misses: int                  # FCP plans built; 0 = no executor
    residual: dict | None = None
    step_fn: Callable | None = None
    batch: dict | None = None


def main(argv=None) -> TrainResult:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default=None,
                   help="assigned shape cell (sets seq/batch)")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced smoke config")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--mesh", default="1x1",
                   help="DxM (data x model) or PxDxM host-device mesh")
    p.add_argument("--dist", default="uniform",
                   choices=["uniform", "real_world", "less_long_tailed",
                            "bimodal"])
    p.add_argument("--block-size", type=int, default=1024)
    p.add_argument("--attn-impl", default="xla",
                   choices=["xla", "pallas", "fused", "fused_xla"],
                   help="executor attention kernel: per-step (xla/pallas)"
                        " or one fused launch per run (fused = Pallas,"
                        " fused_xla = batched-XLA fallback)")
    p.add_argument("--attn-block-q", type=int, default=256,
                   help="kernel q tile (pallas/fused impls)")
    p.add_argument("--attn-block-k", type=int, default=256,
                   help="kernel kv tile (pallas/fused impls)")
    p.add_argument("--attn-interpret", action="store_true",
                   help="run pallas impls in interpret mode (CPU)")
    p.add_argument("--attn-mask", default="causal",
                   help="run-wide attention-mask family: causal | full |"
                        " swa:4096 | chunked:8192.  Models with a"
                        " per-layer attn_mask_pattern in their config"
                        " override this; each distinct mask gets its own"
                        " FCP schedule (per-layer-group scheduling)")
    p.add_argument("--coalesce", type=int, default=16,
                   help="bottom-up coalescer degree C (1 = off)")
    p.add_argument("--comm-dtype", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="wire format of every FCP ppermute payload:"
                        " f32 = exact passthrough, bf16 = ~2x fewer"
                        " comm bytes, int8 = ~3.7x with per-(block,"
                        " head) scales (bounded activation/grad error)")
    p.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="software-pipelined executor rounds: issue round"
                        " r+1's sends before run r's compute and land"
                        " arrivals in double-buffered receive slots, so"
                        " the wire overlaps the fused kernel"
                        " (docs/overlap.md)")
    p.add_argument("--layer-pipeline",
                   action=argparse.BooleanOptionalAction, default=False,
                   help="keep the hidden state resident in the schedule"
                        " layout across each run of same-mask layers —"
                        " one reshuffle per layer-group boundary instead"
                        " of per-layer Q/K/V reshuffles + O restores"
                        " (docs/overlap.md)")
    p.add_argument("--plan-buckets", type=int, default=0,
                   help="canonical length-bucket edges per doubling"
                        " (0 = raw lengths; >0 bounds the schedule-key"
                        " space so the plan cache hits on fresh streams)")
    p.add_argument("--plan-cache-size", type=int, default=64,
                   help="LRU capacity of the schedule/plan cache")
    p.add_argument("--plan-ahead", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="plan batch t+1 on a host thread while t runs")
    p.add_argument("--fresh-stream", action="store_true",
                   help="sample a new composition every step instead of"
                        " round-robining the loader's bounded set")
    p.add_argument("--tokens-per-worker", type=int, default=8192)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--override", action="append", default=[])
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=10,
                   help="periodic-checkpoint cadence in steps (bounds"
                        " the steps lost to a mid-step worker failure)")
    p.add_argument("--supervised", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="fault-tolerant supervised loop for FCP runs"
                        " (single- or multi-pod): health telemetry,"
                        " closed-loop straggler demotion, pod-level"
                        " failure domains with overlapping recovery,"
                        " checkpoint/replay recovery (--no-supervised"
                        " forces the plain loop)")
    p.add_argument("--health-window", type=int, default=8,
                   help="consecutive straggler observations before a"
                        " demotion replan fires (hysteresis)")
    p.add_argument("--straggler-threshold", type=float, default=0.8,
                   help="relative speed below which a worker is a"
                        " straggler")
    p.add_argument("--step-timeout", type=float, default=60.0,
                   help="heartbeat timeout (s) declaring a worker lost")
    p.add_argument("--demote-cooldown", type=int, default=16,
                   help="minimum steps between demote/promote replans"
                        " (rate-limits plan churn)")
    p.add_argument("--log-every", type=int, default=1)
    args = p.parse_args(argv)
    enable_compile_cache()

    dims = [int(x) for x in args.mesh.split("x")]
    if len(dims) == 2:
        mesh_axes = ("data", "model")
    elif len(dims) == 3:
        mesh_axes = ("pod", "data", "model")
    else:
        raise SystemExit("--mesh must be DxM or PxDxM")
    from .mesh import make_mesh
    mesh = make_mesh(tuple(dims), mesh_axes)
    n_cp = dict(zip(mesh_axes, dims)).get("data", 1)
    pods = dict(zip(mesh_axes, dims)).get("pod", 1)
    tp = dict(zip(mesh_axes, dims)).get("model", 1)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = apply_overrides(cfg, args.override)
    # attention-impl selection lives in ParallelConfig so every schedule
    # rebuild — including elastic replans — keeps the same kernel path
    pcfg = ParallelConfig(block_size=args.block_size,
                          coalesce=args.coalesce,
                          attention_impl=args.attn_impl,
                          attn_block_q=args.attn_block_q,
                          attn_block_k=args.attn_block_k,
                          attn_interpret=args.attn_interpret,
                          attn_mask=args.attn_mask,
                          comm_dtype=args.comm_dtype,
                          in_dtype_bytes=_param_dtype_bytes(cfg),
                          overlap=args.overlap,
                          layer_pipeline=args.layer_pipeline,
                          plan_buckets=args.plan_buckets,
                          plan_cache_size=args.plan_cache_size,
                          plan_ahead=args.plan_ahead,
                          health_window=args.health_window,
                          straggler_threshold=args.straggler_threshold,
                          step_timeout=args.step_timeout,
                          demote_cooldown=args.demote_cooldown,
                          checkpoint_every=args.checkpoint_every)
    tcfg = TrainConfig(lr=args.lr, warmup_steps=2, total_steps=args.steps)

    if (args.supervised and cfg.uses_attention and n_cp > 1
            and tp == 1):
        # FCP under the fault-tolerant supervised loop (health
        # telemetry + closed-loop demotion + pod-level failure domains
        # + checkpoint/replay recovery); TP topologies keep the plain
        # loop below
        sup = Supervisor(cfg, pcfg, tcfg, n_workers=n_cp,
                         tokens_per_worker=args.tokens_per_worker,
                         pods=pods, dist=args.dist,
                         fresh=args.fresh_stream,
                         checkpoint_dir=args.checkpoint_dir)
        summary = sup.run(args.steps)
        s = sup.plan_cache.stats
        print(f"plan cache: {s.hits} hits / {s.misses} misses "
              f"(hit rate {s.hit_rate:.2f}), "
              f"{sup.plan_cache.n_unique_specs} static specs, "
              f"{summary['plan_ahead_hits']} plan-ahead builds consumed")
        print(f"health: {len(summary['events'])} event(s), "
              f"{len(summary['recoveries'])} recover(ies), "
              f"{summary['compiles']} compiles")
        print("done.")
        return TrainResult(losses=[r.loss for r in sup.history],
                           params=sup.params, opt=sup.opt,
                           plan_misses=s.misses)

    model = Model(cfg, tp=tp)
    loader = SyntheticLoader(
        dist=args.dist, n_frames=n_cp,
        tokens_per_worker=args.tokens_per_worker,
        vocab_size=cfg.vocab_size, pods=pods, seed=tcfg.seed,
        plan_buckets=pcfg.plan_buckets, bucket_min_len=pcfg.block_size,
        fresh=args.fresh_stream)

    params, opt = init_state(model, mesh, tcfg.seed)
    residual = (compression.init_residuals(params)
                if tcfg.grad_compression else None)

    # amortized planning: repeated canonical layouts skip the planner
    # (plan cache) and the jitted step cache (keyed on the same key), and
    # batch t+1 is planned on a host thread while batch t executes
    plan_cache = pc.PlanCache(pcfg.plan_cache_size)
    planner = pc.PlanAheadPlanner(plan_cache, enabled=pcfg.plan_ahead)
    # FCP on every attention model, one worker included: the planner,
    # plan cache and executor are the training path on one chip too
    fcp = cfg.uses_attention
    # per-layer-group scheduling: one FCP schedule (and one plan-cache
    # key) per distinct mask family in the model; layers route to their
    # group's attention closure
    layer_masks = layer_mask_specs(cfg, pcfg)
    group_masks = list(dict.fromkeys(layer_masks))

    def plan_of(seqlens, mask):
        key = schedule_plan_key(cfg, pcfg, seqlens, n_cp,
                                args.tokens_per_worker, mask=mask)
        build = functools.partial(build_schedule, cfg, pcfg, seqlens,
                                  n_cp, args.tokens_per_worker, mask=mask)
        return key, build

    step_cache: dict = {}
    mgr = None
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir)

    losses = []
    step_fn = batch = None
    t0 = time.time()
    for step in range(args.steps):
        b = loader.next()
        batch = batch_arrays(b, cfg)
        if fcp:
            scheds: dict[MaskSpec, Schedule] = {}
            keys = []
            nxt = loader.peek_seqlens() if step + 1 < args.steps else None
            for m in group_masks:
                key_m, build_m = plan_of(b.seqlens, m)
                scheds[m] = planner.get(key_m, build_m)
                keys.append(key_m)
                if nxt is not None:
                    # plan batch t+1 while this step compiles/executes
                    planner.prefetch(*plan_of(nxt, m))
            key = tuple(keys)
        else:
            key, scheds = b.composition_id, None
        if key not in step_cache:
            if not fcp:
                attn = None
            elif pcfg.layer_pipeline:
                attn = make_pipelined_attn_fns(cfg, pcfg, layer_masks,
                                               scheds, mesh)
            else:
                attn = route_layers(
                    cfg, layer_masks, group_masks,
                    lambda m: make_fcp_attn_fn(scheds[m], mesh, pcfg))
            ts = build_train_step(model, mesh, pcfg, tcfg, attn)
            step_cache[key] = jit_train_step(
                ts, mesh, params, opt, residual, batch)
            while len(step_cache) > max(pcfg.plan_cache_size, 1):
                # bound compiled-step retention like the plan cache
                step_cache.pop(next(iter(step_cache)))
        step_fn = step_cache[key]
        params, opt, residual, loss, gnorm = step_fn(
            params, opt, residual, batch)
        losses.append(loss)
        if step % args.log_every == 0:
            print(f"step {step:5d}  loss {float(loss):.4f}  "
                  f"gnorm {float(gnorm):.3f}  "
                  f"({time.time() - t0:.1f}s)", flush=True)
        if mgr and (step + 1) % max(pcfg.checkpoint_every, 1) == 0:
            mgr.save(step, {"params": params, "opt": opt},
                     extra={"loader": loader.state.to_dict()},
                     blocking=False)
    planner.shutdown()
    if mgr:
        mgr.wait()
    s = plan_cache.stats
    if fcp:
        print(f"plan cache: {s.hits} hits / {s.misses} misses "
              f"(hit rate {s.hit_rate:.2f}), "
              f"{plan_cache.n_unique_specs} static specs, "
              f"{planner.prefetched_hits} plan-ahead builds consumed")
    print("done.")
    return TrainResult(losses=[float(x) for x in losses], params=params,
                       opt=opt, residual=residual, plan_misses=s.misses,
                       step_fn=step_fn, batch=batch)


if __name__ == "__main__":
    main()
