"""Weights from the seed, on the device, in one jitted call.

The tree has the layout the trainer takes (stacked layers under
``layers``); values are drawn here, by the benchmark, so the reference
can draw the same ones again without reading anything the program
made.  Scales follow the usual fan-in rule; biases are drawn small and
non-zero so that the bias path carries signal."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .model_cfg import Dims


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_params(dims: Dims, key: jax.Array) -> dict:
    dt = jnp.dtype(dims.param_dtype)
    L, d, h, kh = dims.n_layers, dims.d_model, dims.n_heads, dims.n_kv_heads
    dh, ff, v = dims.head_dim, dims.d_ff, dims.vocab
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dt)

    lyr = {
        "ln1": jnp.ones((L, d), dt),
        "ln2": jnp.ones((L, d), dt),
        "wq": normal((L, d, h, dh), d ** -0.5),
        "wk": normal((L, d, kh, dh), d ** -0.5),
        "wv": normal((L, d, kh, dh), d ** -0.5),
        "wo": normal((L, h, dh, d), (h * dh) ** -0.5),
        "wi": normal((L, d, ff), d ** -0.5),
        "wg": normal((L, d, ff), d ** -0.5),
        "wdown": normal((L, ff, d), ff ** -0.5),
    }
    if dims.qkv_bias:
        lyr["bq"] = normal((L, h, dh), 0.02)
        lyr["bk"] = normal((L, kh, dh), 0.02)
        lyr["bv"] = normal((L, kh, dh), 0.02)
    return {
        "embed": normal((v, d), 1.0),
        "layers": lyr,
        "final_norm": jnp.ones((d,), dt),
        "lm_head": normal((d, v), d ** -0.5),
    }


def maker(dims: Dims, out_shardings=None):
    """``key -> params`` as one jitted program (one compile per
    configuration, whatever the seed)."""
    return jax.jit(lambda key: make_params(dims, key),
                   out_shardings=out_shardings)
