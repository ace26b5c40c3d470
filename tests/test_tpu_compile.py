"""Compile the fused Pallas kernels for a described TPU v5e.

Interpret mode (``tests/test_kernels.py``) runs the kernel bodies on the
CPU but checks none of Mosaic's tiling rules; here the TPU compiler
itself lowers the kernels at ``stablelm_1_6b`` widths (32 heads, bf16
inputs, 256-wide tiles) for a chip that is described, not attached.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import functools
import os
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa

H = KH = 32                       # stablelm_1_6b query / kv heads
TILE = 256
# (head_dim, block size, q slots + trash, extended kv rows, run steps):
# 8192 tokens per worker at block 1024, 16384 at block 4096
SHAPES = {"d64_bs1024": (64, 1024, 9, 12, 36),
          "d128_bs4096": (128, 4096, 5, 7, 10)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # loading the TPU library installs a crash reporter on SIGTERM; put
    # the default back so that a worker stopped at a time limit exits
    # quietly instead of writing a stack trace into the run's output
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fused_args(sharding, d, bs, sl, ex, steps):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    tab = s((steps,), jnp.int32)
    qs = s((sl, H, bs, d), jnp.bfloat16)
    kx = s((ex, KH, bs, d), jnp.bfloat16)
    q_meta = s((sl, bs), jnp.int32)
    k_meta = s((steps, bs), jnp.int32)
    o = s((sl, H, bs, d), jnp.float32)
    lse = s((sl, H, bs), jnp.float32)
    fwd = (tab, tab, qs, kx, kx, q_meta, q_meta, k_meta, k_meta, o, lse)
    bwd = (tab, tab, qs, kx, kx, q_meta, q_meta, k_meta, k_meta, lse, o,
           lse)
    return fwd, bwd


def _compile(fn, args):
    step = functools.partial(fn, block_q=TILE, block_k=TILE)
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", ["fused_flash_fwd", "fused_flash_bwd_dq",
                                    "fused_flash_bwd_dkv"])
def test_fused_kernel_compiles_for_v5e(one_chip, kernel, shape):
    fwd, bwd = _fused_args(one_chip, *SHAPES[shape])
    _compile(getattr(fa, kernel), fwd if kernel == "fused_flash_fwd"
             else bwd)


@pytest.mark.parametrize("kernel", ["flash_attention_fwd",
                                    "flash_attention_bwd"])
def test_per_step_kernel_compiles_for_v5e(one_chip, kernel):
    d, bs = SHAPES["d64_bs1024"][:2]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q = s((H, bs, d), jnp.bfloat16)
    k = s((KH, bs, d), jnp.bfloat16)
    meta = s((bs,), jnp.int32)
    args = (q, k, k, meta, meta, meta, meta)
    if kernel == "flash_attention_bwd":
        o = s((H, bs, d), jnp.float32)
        lse = s((H, bs), jnp.float32)
        args += (o, lse, o, lse)
    _compile(getattr(fa, kernel), args)
