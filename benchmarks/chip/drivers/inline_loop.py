"""One-worker FCP training, driven as ``repro.launch.train.main`` drives
its inline loop: ``SyntheticLoader`` -> ``batch_arrays`` ->
``PlanCache``/``PlanAheadPlanner`` under ``schedule_plan_key`` and
``build_schedule`` (batch t+1 planned while t runs) -> ``route_layers``
/ ``make_fcp_attn_fn`` -> ``build_train_step`` -> ``jit_train_step``,
one compiled step per plan key -> the step, then the host sync on the
loss.  The calls and their order are ``main``'s; this copy goes once
that loop is a function of the program's own.

What the benchmark supplies instead of ``main``: the weights (drawn by
the cell's architecture, ``cell.arch.maker``, from the seed, in their
train shardings), the loader's seed (the run's), and the loader's
bounded set of compositions (the traffic file's, in the order the seed
gives them).  :func:`program_configs` and :func:`seeded_state` are
shared with the other drivers of a dense cell.

Each phase of a step runs inside a host span (``bench.loader``,
``bench.batch``, ``bench.plan``, ``bench.build``, ``bench.dispatch``,
``bench.sync``), which the trace reduction uses to say what the host was
doing while the device idled.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import reference, traffic_gen


@dataclasses.dataclass
class Step:
    tokens: np.ndarray          # [F, T] token ids the step trained on
    seqlens: list
    loss: float


def program_configs(cell, dims, seed: int, hp):
    """The program's model, parallel and train configurations of a dense
    cell: the sizes as run, the traffic's ``parallel`` block, the
    traffic's hyper-parameters and the run's seed."""
    from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
    from repro.launch import train

    cfg = ModelConfig(
        name=cell.config_name, family="dense", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_ff,
        vocab_size=dims.vocab, head_dim=dims.head_dim,
        qkv_bias=dims.qkv_bias, rope_theta=dims.rope_theta,
        norm_eps=dims.norm_eps, param_dtype=dims.param_dtype)
    pcfg = ParallelConfig(
        block_size=int(cell.traffic["block_size"]),
        in_dtype_bytes=train._param_dtype_bytes(cfg),
        **cell.traffic.get("parallel", {}))
    tcfg = TrainConfig(
        lr=hp.lr, warmup_steps=hp.warmup_steps,
        total_steps=hp.total_steps, weight_decay=hp.weight_decay,
        grad_clip=hp.grad_clip, b1=hp.b1, b2=hp.b2, seed=int(seed))
    return cfg, pcfg, tcfg


def seeded_state(cell, dims, seed: int, mesh):
    """Weights from the seed and fresh AdamW state, each made on the
    device in one jitted call, in the trainer's shardings on ``mesh``."""
    from repro.launch import train
    from repro.optimizer import adamw

    make = cell.arch.maker(dims)
    key = reference.seed_key(seed)
    p_like = jax.eval_shape(make, key)
    o_like = jax.eval_shape(adamw.init, p_like)
    psh, osh = train._state_shardings(p_like, o_like, mesh)
    params = cell.arch.maker(dims, out_shardings=psh)(key)
    return params, jax.jit(adamw.init, out_shardings=osh)(params)


class Driver:
    def __init__(self, cell, dims, seed: int, hp):
        from repro.core import plan_cache as pc
        from repro.data.loader import SyntheticLoader
        from repro.launch import train
        from repro.launch.mesh import make_mesh
        from repro.models import Model

        self.train = train
        tr = cell.traffic
        n_cp, tpw = int(tr["workers"]), int(tr["tokens_per_worker"])
        self.n_cp, self.tpw = n_cp, tpw
        self.cfg, self.pcfg, self.tcfg = program_configs(cell, dims, seed,
                                                         hp)
        self.mesh = make_mesh((n_cp, 1), ("data", "model"))
        self.model = Model(self.cfg, tp=1)

        self.compositions = traffic_gen.ordered(tr, seed)
        self.loader = SyntheticLoader(
            dist="uniform", n_frames=n_cp, tokens_per_worker=tpw,
            vocab_size=self.cfg.vocab_size, pods=1, seed=self.tcfg.seed,
            uniform_len=n_cp * tpw, n_buckets=len(self.compositions),
            plan_buckets=self.pcfg.plan_buckets,
            bucket_min_len=self.pcfg.block_size, fresh=False)
        self.loader.compositions = [list(c) for c in self.compositions]

        self.params, self.opt = seeded_state(cell, dims, seed, self.mesh)
        self.residual = None

        self.plan_cache = pc.PlanCache(self.pcfg.plan_cache_size)
        self.planner = pc.PlanAheadPlanner(self.plan_cache,
                                           enabled=self.pcfg.plan_ahead)
        self.layer_masks = train.layer_mask_specs(self.cfg, self.pcfg)
        self.group_masks = list(dict.fromkeys(self.layer_masks))
        self.step_cache: dict = {}

    def _plan_of(self, seqlens, mask):
        key = self.train.schedule_plan_key(self.cfg, self.pcfg, seqlens,
                                           self.n_cp, self.tpw, mask=mask)
        build = functools.partial(self.train.build_schedule, self.cfg,
                                  self.pcfg, seqlens, self.n_cp, self.tpw,
                                  mask=mask)
        return key, build

    def step(self) -> Step:
        train = self.train
        with TraceAnnotation("bench.loader"):
            b = self.loader.next()
        with TraceAnnotation("bench.batch"):
            batch = train.batch_arrays(b, self.cfg)
        with TraceAnnotation("bench.plan"):
            scheds, keys = {}, []
            nxt = self.loader.peek_seqlens()
            for m in self.group_masks:
                key_m, build_m = self._plan_of(b.seqlens, m)
                scheds[m] = self.planner.get(key_m, build_m)
                keys.append(key_m)
                self.planner.prefetch(*self._plan_of(nxt, m))
            key = tuple(keys)
        if key not in self.step_cache:
            with TraceAnnotation("bench.build"):
                attn = train.route_layers(
                    self.cfg, self.layer_masks, self.group_masks,
                    lambda m: train.make_fcp_attn_fn(scheds[m], self.mesh,
                                                     self.pcfg))
                ts = train.build_train_step(self.model, self.mesh,
                                            self.pcfg, self.tcfg, attn)
                self.step_cache[key] = train.jit_train_step(
                    ts, self.mesh, self.params, self.opt, self.residual,
                    batch)
                while len(self.step_cache) > max(self.pcfg.plan_cache_size,
                                                 1):
                    self.step_cache.pop(next(iter(self.step_cache)))
        fn = self.step_cache[key]
        with TraceAnnotation("bench.dispatch"):
            self.params, self.opt, self.residual, loss, _ = fn(
                self.params, self.opt, self.residual, batch)
        with TraceAnnotation("bench.sync"):
            loss = float(loss)
        return Step(tokens=b.tokens, seqlens=list(b.seqlens), loss=loss)

    def free(self) -> None:
        """Drop the trained state and the compiled steps, and stop the
        plan-ahead thread, so the device is empty for the reference."""
        self.planner.shutdown()
        self.step_cache.clear()
        self.params = self.opt = self.residual = None
