"""Single-device integration tests of the assembled train step: jit with
shardings, grad compression path, schedule-bucket compile caching, and
speed-aware rebalancing wiring."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.configs.base import ParallelConfig, TrainConfig
from repro.data import SyntheticLoader
from repro.launch import train as T
from repro.launch.mesh import make_mesh
from repro.models import Model, dense_attn_fn
from repro.optimizer import adamw
from repro.runtime import compression


def _setup(grad_compression=False, steps=8):
    cfg = smoke_config("stablelm_1_6b").replace(param_dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg, tp=1)
    pcfg = ParallelConfig(remat=False)
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=steps,
                       grad_compression=grad_compression)
    loader = SyntheticLoader(dist="uniform", uniform_len=512, n_frames=1,
                             tokens_per_worker=2048,
                             vocab_size=cfg.vocab_size, seed=0)
    params = model.init(jax.random.key(0))
    opt = adamw.init(params)
    residual = compression.init_residuals(params) if grad_compression \
        else None
    return cfg, mesh, model, pcfg, tcfg, loader, params, opt, residual


def _run(grad_compression, steps=8):
    cfg, mesh, model, pcfg, tcfg, loader, params, opt, residual = _setup(
        grad_compression, steps)
    losses = []
    step_fn = None
    for _ in range(steps):
        b = loader.next()
        batch = T.batch_arrays(b, cfg)
        if step_fn is None:
            attn = dense_attn_fn(jnp.asarray(b.seg_ids),
                                 batch["positions"])
            fn = T.build_train_step(model, mesh, pcfg, tcfg, attn)
            step_fn = T.jit_train_step(fn, mesh, params, opt, residual,
                                       batch)
        params, opt, residual, loss, gnorm = step_fn(params, opt,
                                                     residual, batch)
        losses.append(float(loss))
        assert np.isfinite(losses[-1])
    return losses, residual


def test_train_step_loss_decreases():
    losses, _ = _run(grad_compression=False, steps=10)
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_train_step_with_grad_compression():
    """bf16 error-feedback compression trains and stays close to the
    uncompressed loss trajectory."""
    plain, _ = _run(grad_compression=False, steps=8)
    comp, residual = _run(grad_compression=True, steps=8)
    assert np.mean(comp[-3:]) < np.mean(comp[:3])        # still learns
    # trajectories stay within a few percent of each other
    np.testing.assert_allclose(comp, plain, rtol=0.1)
    # error feedback is active (non-zero residuals)
    rn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(residual))
    assert rn > 0


def test_schedule_bucket_reuse():
    """Same length composition -> same StaticSpec (one compile per
    bucket: the schedule-class static compilation contract)."""
    cfg = smoke_config("stablelm_1_6b").replace(param_dtype="float32")
    pcfg = ParallelConfig(block_size=256)
    loader = SyntheticLoader(dist="real_world", n_frames=4,
                             tokens_per_worker=1024,
                             vocab_size=cfg.vocab_size, n_buckets=2,
                             seed=1)
    specs = {}
    for _ in range(4):
        b = loader.next()
        sched = T.build_schedule(cfg, pcfg, b.seqlens, 4, 1024)
        specs.setdefault(b.composition_id, sched.spec)
        assert specs[b.composition_id] == sched.spec   # hashable + equal


def test_speed_aware_schedule_shifts_load():
    cfg = smoke_config("stablelm_1_6b")
    pcfg = ParallelConfig(block_size=256, locality="off")
    seqlens = [2048] * 8
    speeds = np.array([1.0, 1.0, 1.0, 0.25])
    sched = T.build_schedule(cfg, pcfg, seqlens, 4, 4096, speeds=speeds)
    from repro.core import cost_model as cm
    costs = cm.block_q_flops(sched.batch, sched.deps, cfg.n_heads,
                             cfg.head_dim)
    loads = np.bincount(sched.assignment, weights=costs, minlength=4)
    assert loads[3] < 0.6 * loads[:3].mean()


def test_per_layer_group_attention_routing():
    """Per-layer attn-fn sequences: uniform sequence == scanned single
    fn, and a mixed mask pattern actually changes the logits."""
    from repro import masks

    cfg = smoke_config("stablelm_1_6b").replace(param_dtype="float32")
    pcfg = ParallelConfig(remat=False)
    pat_cfg = cfg.replace(attn_mask_pattern=("swa:256", "causal"))
    specs = T.layer_mask_specs(pat_cfg, pcfg)
    assert len(specs) == cfg.n_layers
    assert specs[0] == masks.sliding_window(256)
    assert specs[1] == masks.CAUSAL
    # --attn-mask drives every layer when the config has no pattern
    assert set(T.layer_mask_specs(
        cfg, ParallelConfig(attn_mask="swa:512"))) == \
        {masks.sliding_window(512)}

    model = Model(cfg, tp=1)
    loader = SyntheticLoader(dist="uniform", uniform_len=512, n_frames=1,
                             tokens_per_worker=1024,
                             vocab_size=cfg.vocab_size, seed=3)
    b = loader.next()
    batch = T.batch_arrays(b, cfg)
    params = model.init(jax.random.key(0))
    seg = jnp.asarray(b.seg_ids)
    attn = dense_attn_fn(seg, batch["positions"])
    logits_scan = np.asarray(model.forward(params, batch, attn))
    logits_unroll = np.asarray(
        model.forward(params, batch, (attn,) * cfg.n_layers))
    np.testing.assert_allclose(logits_unroll, logits_scan, atol=2e-4,
                               rtol=2e-4)
    attn_swa = dense_attn_fn(seg, batch["positions"],
                             mask=masks.sliding_window(64))
    mixed = np.asarray(model.forward(
        params, batch, (attn_swa,) + (attn,) * (cfg.n_layers - 1)))
    assert np.abs(mixed - logits_scan).max() > 1e-3


def test_train_step_with_mixed_mask_pattern():
    """The assembled train step learns with an interleaved mask pattern
    (per-layer attn routing through build_train_step)."""
    from repro import masks

    cfg = smoke_config("stablelm_1_6b").replace(
        param_dtype="float32", attn_mask_pattern=("swa:256", "causal"))
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg, tp=1)
    pcfg = ParallelConfig(remat=False)
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=8)
    loader = SyntheticLoader(dist="uniform", uniform_len=512, n_frames=1,
                             tokens_per_worker=1024,
                             vocab_size=cfg.vocab_size, seed=0)
    params = model.init(jax.random.key(0))
    opt = adamw.init(params)
    layer_masks = T.layer_mask_specs(cfg, pcfg)
    assert len(set(layer_masks)) == 2
    losses = []
    step_fn = None
    for _ in range(8):
        b = loader.next()
        batch = T.batch_arrays(b, cfg)
        if step_fn is None:
            seg = jnp.asarray(b.seg_ids)
            attn = tuple(dense_attn_fn(seg, batch["positions"], mask=m)
                         for m in layer_masks)
            fn = T.build_train_step(model, mesh, pcfg, tcfg, attn)
            step_fn = T.jit_train_step(fn, mesh, params, opt, None, batch)
        params, opt, _, loss, _ = step_fn(params, opt, None, batch)
        losses.append(float(loss))
        assert np.isfinite(losses[-1])
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_fused_output_saved_on_one_run_plans_only():
    """The executor names each layer's fused output for the "dots" remat
    policy on a one-run plan (one worker), so the grad launches one
    ``fused_flash_fwd`` a layer; a multi-worker plan with comm rounds
    names none.  Trace only."""
    from jax.sharding import AbstractMesh

    from _jaxpr import count_kernel_calls
    from repro.kernels import ops

    cfg, mesh, model, _, _, loader, params, _, _ = _setup()
    pcfg = ParallelConfig(block_size=256, attention_impl="fused",
                          attn_interpret=True, attn_block_q=128,
                          attn_block_k=128)
    b = loader.next()
    batch = T.batch_arrays(b, cfg)
    sched = T.build_schedule(cfg, pcfg, b.seqlens, 1, 2048)
    assert sched.spec.n_runs == 1
    attn = (T.make_fcp_attn_fn(sched, mesh, pcfg),) * cfg.n_layers

    def loss(p):
        return model.loss(p, batch, attn, remat="dots")

    c = ops.count_attention_launches(loss, params)
    assert c["fused"] == c["fused_saved"] == cfg.n_layers, c
    grad = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert count_kernel_calls(grad, "_fused_fwd_kernel") == cfg.n_layers

    n = 4
    b4 = SyntheticLoader(dist="real_world", n_frames=n,
                         tokens_per_worker=1024, vocab_size=cfg.vocab_size,
                         seed=0, bucket_min_len=256).next()
    sched4 = T.build_schedule(cfg, pcfg, b4.seqlens, n, 1024)
    assert sched4.spec.n_rounds > 0
    attn4 = T.make_fcp_attn_fn(
        sched4, AbstractMesh((n, 1), ("data", "model")), pcfg)
    q = jax.ShapeDtypeStruct((n, 1024, cfg.n_heads, cfg.head_dim),
                             jnp.float32)
    kv = jax.ShapeDtypeStruct((n, 1024, cfg.n_kv_heads, cfg.head_dim),
                              jnp.float32)
    c4 = ops.count_attention_launches(attn4, q, kv, kv)
    assert c4["fused"] > 1 and c4["fused_saved"] == 0, c4


def _restore_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def test_one_worker_main_trains_through_executor(tmp_path, monkeypatch):
    """``train.main`` on a 1x1 mesh trains through the planner and the
    FCP executor (``fused_xla``), and its step-0 loss matches the dense
    single-device oracle on the same parameters and batch."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        res = T.main(["--arch", "stablelm_1_6b", "--smoke", "--mesh", "1x1",
                      "--attn-impl", "fused_xla", "--dist", "real_world",
                      "--tokens-per-worker", "1024", "--block-size", "256",
                      "--steps", "1", "--override", "param_dtype=float32"])
    finally:
        _restore_compile_cache()
    assert res.plan_misses == 1          # a schedule was planned and run

    cfg = smoke_config("stablelm_1_6b").replace(param_dtype="float32")
    model = Model(cfg, tp=1)
    tcfg = TrainConfig()
    params, _ = T.init_state(model, make_mesh((1, 1), ("data", "model")),
                             tcfg.seed)
    b = SyntheticLoader(dist="real_world", n_frames=1,
                        tokens_per_worker=1024, vocab_size=cfg.vocab_size,
                        seed=tcfg.seed, bucket_min_len=256).next()
    batch = T.batch_arrays(b, cfg)
    attn = dense_attn_fn(jnp.asarray(b.seg_ids), batch["positions"])
    oracle = float(jax.jit(lambda p: model.loss(p, batch, attn))(params))
    # the suite's executor-vs-dense-oracle tolerance, normalized
    assert abs(res.losses[0] - oracle) <= 1e-6 * max(1.0, abs(oracle)), \
        (res.losses[0], oracle)


def test_compile_cache_dir(tmp_path, monkeypatch):
    """The entry points' compile cache lives where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at ``.jax_cache/`` in the
    checkout (a fixed path: the cache key includes it)."""
    from repro.launch.compile_cache import enable_compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            root / ".jax_cache")
    finally:
        _restore_compile_cache()
