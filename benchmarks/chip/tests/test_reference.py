"""The reference train step against the program's model at smoke size:
float32 weights, the executor's ``fused_xla`` attention on one worker."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tests import tiny
from chipbench import reference, traffic_gen, weights
from chipbench.model_cfg import Dims


def test_reference_matches_program_model():
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.launch import train
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    dims = Dims.from_config(dict(tiny.CONFIG, param_dtype="float32"))
    cfg = ModelConfig(
        name="tiny", family="dense", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_ff, vocab_size=dims.vocab,
        head_dim=dims.head_dim, qkv_bias=True, rope_theta=dims.rope_theta,
        norm_eps=dims.norm_eps, param_dtype="float32")
    tpw = tiny.TRAFFIC["tokens_per_worker"]
    pcfg = ParallelConfig(block_size=tiny.TRAFFIC["block_size"],
                          attention_impl="fused_xla", in_dtype_bytes=4)
    seqlens = traffic_gen.compositions(tiny.TRAFFIC)[0]
    assert len(seqlens) > 1
    tokens = np.random.default_rng(1).integers(1, dims.vocab, tpw)
    params = weights.maker(dims)(weights.seed_key(5))

    mesh = make_mesh((1, 1), ("data", "model"))
    sched = train.build_schedule(cfg, pcfg, seqlens, 1, tpw)
    attn = train.make_fcp_attn_fn(sched, mesh, pcfg)
    b = reference.derive(tokens, seqlens)
    batch = {"tokens": jnp.asarray(b["tokens"])[None],
             "labels": jnp.asarray(b["labels"])[None],
             "positions": jnp.asarray(b["pos"])[None],
             "loss_mask": jnp.asarray(b["mask"])[None]}
    model = Model(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            functools.partial(model.loss, batch=batch, attn_fn=attn)))(
                params)
    ref_loss, ref_grads = reference.loss_and_grads(dims, params, tokens,
                                                   seqlens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    flat, _ = jax.tree.flatten(grads)
    ref_flat, _ = jax.tree.flatten(ref_grads)
    assert len(flat) == len(ref_flat) == 15
    for g, r in zip(flat, ref_flat):
        scale = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * scale + 1e-9
