"""The reference train step against the program's model at smoke size:
float32 weights, the executor's ``fused_xla`` attention on one worker."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tests import tiny
from chipbench import reference, traffic_gen
from chipbench.archs import dense


def test_reference_matches_program_model():
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.launch import train
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    dims = dense.sizes(dict(tiny.CONFIG, param_dtype="float32"))
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
    params = dense.maker(dims)(reference.seed_key(5))

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
    ref_loss, ref_grads = dense.loss_and_grads(dims, params, tokens,
                                               seqlens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    flat, _ = jax.tree.flatten(grads)
    ref_flat, _ = jax.tree.flatten(ref_grads)
    assert len(flat) == len(ref_flat) == 15
    for g, r in zip(flat, ref_flat):
        scale = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * scale + 1e-9


# the tiny cell's first three reference steps (seed 3000000019, tokens
# from default_rng(0)) as the reference read them before the model moved
# into archs/dense.py
PINNED_LOSSES = [6.02904748916626, 6.072024345397949, 6.003844261169434]
PINNED_GRAD_NORMS = {
    "embed": 0.05163555499166251, "final_norm": 0.13481729663908484,
    "layers/bk": 0.006693116738460959, "layers/bq": 0.009112859261222185,
    "layers/bv": 0.06255078595131637, "layers/ln1": 0.01911438652314246,
    "layers/ln2": 0.036224490031600005, "layers/wdown": 0.25899717584252363,
    "layers/wg": 0.1843296550214291, "layers/wi": 0.18110161647200587,
    "layers/wk": 0.0739551614969969, "layers/wo": 0.09020064957439901,
    "layers/wq": 0.0754158850759268, "layers/wv": 0.09385434910655023,
    "lm_head": 0.3654003888368607}


def test_tiny_reference_as_pinned_before_the_move():
    seed = 3_000_000_019
    rng = np.random.default_rng(0)
    batches = [(rng.integers(1, tiny.CONFIG["vocab_size"], sum(lens)), lens)
               for lens in traffic_gen.ordered(tiny.TRAFFIC, seed)[:3]]
    ref = dense.run(dense.sizes(tiny.CONFIG),
                    reference.Hyper.from_traffic(tiny.TRAFFIC), seed,
                    batches)
    assert ref.losses == PINNED_LOSSES
    assert ref.grad_norms == PINNED_GRAD_NORMS
