"""Serving steps: batched prefill (FCP attention) and CP decode.

* ``build_prefill_step`` — packed-stream forward through FCP attention,
  emitting logits + the KV cache re-laid-out as ``[L, B, S, KH, D]``
  (stream order is sequence-major for uniform shapes, so this is a
  reshape, not a shuffle).  SSM/hybrid prefill emits recurrent states.
* ``build_decode_step`` — one token for the whole batch against a
  sequence-sharded cache: ``cp_decode_attention`` (pmax/psum flash merge)
  + ``cp_cache_update`` (collective-free masked write).

The decode_32k cell shards cache over (data: batch, model: sequence);
long_500k (batch=1) shards sequence over (data, model) jointly — the only
way 524K tokens x layers of cache fit per chip (DESIGN.md §4.3).

CLI: PYTHONPATH=src python -m repro.launch.serve --arch stablelm_1_6b \
        --smoke --mesh 4x2 --tokens 16
"""

from __future__ import annotations

import argparse
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import (ModelConfig, apply_overrides,
                            get_config, smoke_config)
from ..core import executor as ex
from ..models import Model
from ..models import hybrid as hybridlib
from ..models import ssm as ssmlib
from ..models import transformer as tflib
from ..parallel import sharding as sh
from .compile_cache import enable_compile_cache


def cache_specs(cfg: ModelConfig, mesh, kind: str):
    """PartitionSpecs for decode caches.

    decode_32k: batch over data, cache seq over model.
    long_500k (batch=1): cache seq over (data, model)."""
    if kind == "long":
        batch_axis = None
        seq_axes = tuple(a for a in ("data", "model") if a in
                         mesh.axis_names)
    else:
        batch_axis = "data" if "data" in mesh.axis_names else None
        seq_axes = ("model",) if "model" in mesh.axis_names else ()
    return batch_axis, seq_axes


def build_decode_fns(cfg: ModelConfig, mesh, kind: str,
                     impl: str = "xla"):
    batch_axis, seq_axes = cache_specs(cfg, mesh, kind)
    ecfg = ex.ExecConfig(impl=impl)
    if not seq_axes:
        from ..models import dense_cache_update, dense_decode_attn
        return dense_decode_attn, dense_cache_update, batch_axis, seq_axes
    attn = functools.partial(ex.cp_decode_attention, mesh=mesh,
                             batch_axis=batch_axis, seq_axes=seq_axes,
                             cfg=ecfg)
    upd = functools.partial(ex.cp_cache_update, mesh=mesh,
                            batch_axis=batch_axis, seq_axes=seq_axes)
    return (lambda q, kc, vc, ln: attn(q, kc, vc, ln)), \
        (lambda c, n, p: upd(c, n, p)), batch_axis, seq_axes


def build_decode_step(model: Model, mesh, kind: str, impl: str = "xla"):
    cfg = model.cfg
    attn_fn, upd_fn, batch_axis, seq_axes = build_decode_fns(
        cfg, mesh, kind, impl)

    def decode_step(params, tokens, pos, cache):
        logits, cache = model.decode_step(params, tokens, pos, cache,
                                          attn_fn, upd_fn)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, cache

    return decode_step, batch_axis, seq_axes


def decode_cache_shardings(cache, mesh, batch_axis, seq_axes):
    def one(path, x):
        name = getattr(path[-1], "key", None)
        if name in ("k", "v"):          # [L|G, B, S, KH, D]
            return NamedSharding(mesh, P(None, batch_axis, seq_axes,
                                         None, None))
        if name == "state":             # [L, B, nh, hd, ds]
            return NamedSharding(mesh, P(None, batch_axis, "model"
                                         if "model" in mesh.axis_names
                                         else None, None, None))
        if name == "conv":              # [L, B, cw-1, C]
            return NamedSharding(mesh, P(None, batch_axis, None, "model"
                                         if "model" in mesh.axis_names
                                         else None))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(one, cache)


def jit_decode_step(decode_step, mesh, params_like, cache_like, batch_size,
                    batch_axis, seq_axes, fsdp: bool = False):
    psh = sh.param_shardings(params_like, mesh, mode="serve", fsdp=fsdp)
    csh = decode_cache_shardings(cache_like, mesh, batch_axis, seq_axes)
    tsh = NamedSharding(mesh, P(batch_axis))
    rep = NamedSharding(mesh, P())
    return jax.jit(decode_step,
                   in_shardings=(psh, tsh, tsh, csh),
                   out_shardings=(tsh, NamedSharding(
                       mesh, P(batch_axis, "model"
                               if "model" in mesh.axis_names else None)),
                       csh),
                   donate_argnums=(3,))


# --------------------------------------------------------------------------
# prefill
# --------------------------------------------------------------------------

def build_prefill_step(model: Model, mesh, attn_fn: Callable,
                       batch_size: int, seq_len: int, remat: bool = True,
                       ragged: bool = False):
    """Returns ``prefill_step(params, batch) -> (last_logits, cache)``.

    With ``ragged=True`` (attention families only) the returned step is
    ``prefill_step(params, batch, last_idx)``: each sequence's logits
    are gathered at its *true* last-token index instead of position
    ``seq_len - 1``, so right-padded prompts prefill exactly — under a
    causal mask real tokens never attend the padding, and the padded
    cache tail is masked (then progressively overwritten) at decode
    time.  Recurrent families cannot pad-up exactly (the state scans
    the padding), so they reject ``ragged`` and chunk instead
    (``runtime/serving.py``)."""
    cfg = model.cfg
    if ragged and cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"ragged prefill is exact only for attention families; "
            f"{cfg.family!r} states would scan the padding — chunk the "
            f"prompt instead (round down to a bucket edge and "
            f"teacher-force the tail)")

    def prefill_step(params, batch):
        if cfg.family == "ssm":
            # per-sequence scans (vmap over batch) so each sequence gets
            # its own final state / conv tail
            f, t = batch["tokens"].shape
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
            xb = x.reshape(batch_size, seq_len, cfg.d_model)
            pos_b = batch["positions"].reshape(batch_size, seq_len)

            def scan_fn(xb, lp):
                out, (st, cv) = jax.vmap(
                    lambda xi, pi: ssmlib.mamba_block(
                        xi, lp, cfg, pi, return_state=True))(xb, pos_b)
                return out, (st, cv)

            xb, (states, convs) = jax.lax.scan(scan_fn, xb,
                                               params["mamba"])
            from ..models.layers import rms_norm
            xl = rms_norm(xb[:, -1], params["final_norm"], cfg.norm_eps)
            head = params["embed"].T if cfg.tie_embeddings \
                else params["lm_head"]
            logits = jnp.einsum("bd,dv->bv", xl, head)
            return logits, {"state": states, "conv": convs}
        if cfg.family == "hybrid":
            return hybridlib.forward_prefill(params, cfg, batch, attn_fn,
                                             batch_size, seq_len)
        logits, ks, vs = tflib.forward_prefill(params, cfg, batch, attn_fn,
                                               remat=remat)
        # frames stream -> [L, B, S, KH, D] (stream is sequence-major)
        lyr, f, t, kh, dh = ks.shape
        ks = ks.reshape(lyr, batch_size, seq_len, kh, dh)
        vs = vs.reshape(lyr, batch_size, seq_len, kh, dh)
        # logits of each sequence's last token
        lg = logits.reshape(batch_size, seq_len, -1)[:, -1]
        return lg, {"k": ks, "v": vs}

    if not ragged:
        return prefill_step

    def ragged_prefill_step(params, batch, last_idx):
        if cfg.family == "hybrid":
            raise AssertionError("unreachable: rejected above")
        logits, ks, vs = tflib.forward_prefill(params, cfg, batch, attn_fn,
                                               remat=remat)
        lyr, f, t, kh, dh = ks.shape
        ks = ks.reshape(lyr, batch_size, seq_len, kh, dh)
        vs = vs.reshape(lyr, batch_size, seq_len, kh, dh)
        lg = logits.reshape(batch_size, seq_len, -1)[
            jnp.arange(batch_size), last_idx]
        return lg, {"k": ks, "v": vs}

    return ragged_prefill_step


# --------------------------------------------------------------------------
# CLI driver: continuous-batching serving over a mixed-length stream
# --------------------------------------------------------------------------

def serving_stream(rng, vocab: int, n: int, min_len: int, max_len: int,
                   ) -> list[np.ndarray]:
    """Synthetic mixed-length request stream (uniform prompt lengths)."""
    lens = rng.integers(min_len, max_len + 1, (n,))
    return [rng.integers(1, vocab, (int(L),)).astype(np.int32)
            for L in lens]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--mesh", default="1x1")
    p.add_argument("--cache-len", type=int, default=512)
    p.add_argument("--kind", default="decode", choices=["decode", "long"])
    p.add_argument("--override", action="append", default=[])
    # serving-loop knobs (ServeConfig / runtime/serving.py)
    p.add_argument("--slots", type=int, default=8,
                   help="decode batch slots (continuous batching)")
    p.add_argument("--requests", type=int, default=32,
                   help="synthetic request-stream length")
    p.add_argument("--tokens", type=int, default=16,
                   help="tokens to generate per request")
    p.add_argument("--queue-depth", type=int, default=64)
    p.add_argument("--prefill-impl", default="fcp",
                   choices=["fcp", "dense"],
                   help="bucketed FCP prefill, or the dense escape "
                        "hatch (also the 1-worker path)")
    p.add_argument("--prefill-tokens-per-worker", type=int, default=256)
    p.add_argument("--strict-prefill", action="store_true",
                   help="fail instead of falling back to dense prefill "
                        "when prefill_impl='fcp' is unsupported on the "
                        "mesh (pod axis)")
    p.add_argument("--bucket-min", type=int, default=32,
                   help="smallest prefill bucket edge")
    p.add_argument("--block-size", type=int, default=0,
                   help="FCP scheduling block (0 = auto)")
    p.add_argument("--prompt-len", type=int, default=128,
                   help="max prompt length in the synthetic stream")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    dims = [int(x) for x in args.mesh.split("x")]
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    from .mesh import make_mesh
    mesh = make_mesh(tuple(dims), axes)
    tp = dict(zip(axes, dims)).get("model", 1)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = apply_overrides(cfg, args.override)
    model = Model(cfg, tp=tp)
    params = model.init(jax.random.key(0))

    from ..configs.base import ParallelConfig, ServeConfig
    from ..runtime.serving import ServingLoop
    from .train import _param_dtype_bytes
    tpw = args.prefill_tokens_per_worker
    block = args.block_size or min(4096, tpw)
    pcfg = ParallelConfig(block_size=block,
                          in_dtype_bytes=_param_dtype_bytes(cfg))
    scfg = ServeConfig(
        cache_len=args.cache_len, decode_slots=args.slots,
        queue_depth=args.queue_depth, max_new_tokens=args.tokens,
        prefill_tokens_per_worker=tpw, bucket_min=args.bucket_min,
        prefill_impl=args.prefill_impl, kind=args.kind,
        strict_prefill=args.strict_prefill)
    loop = ServingLoop(model, params, mesh, pcfg, scfg)

    rng = np.random.default_rng(args.seed)
    max_len = min(args.prompt_len, args.cache_len - args.tokens)
    if max_len < 1:
        raise SystemExit("--cache-len must exceed --tokens")

    # warmup: one request per admissible bucket compiles every prefill
    # shape and the decode loop; the measured stream then recompiles
    # nothing
    t0 = time.perf_counter()
    base = loop.warmup()
    warm_s = time.perf_counter() - t0

    stream = serving_stream(rng, cfg.vocab_size, args.requests, 1, max_len)
    report = loop.run(stream, max_new=args.tokens)
    recompiles = (sum(loop.compile_counts().values())
                  - sum(base.values()))

    print(f"warmup {warm_s:.2f}s over buckets {loop.edges} "
          f"({args.prefill_impl} prefill)")
    print(f"served {report['requests']} requests / "
          f"{report['generated_tokens']} tokens in "
          f"{report['wall_s']:.2f}s "
          f"({report['sustained_tok_s']:.1f} tok/s sustained)")
    print(f"prefill: {report['prefill_batches']} batches, fill "
          f"{report['prefill_fill']:.2f}, p99 "
          f"{report['prefill_ms']['p99']:.1f}ms | decode: "
          f"{report['decode_steps']} steps, p99 "
          f"{report['decode_ms']['p99']:.1f}ms | queue p99 "
          f"{report['queue_ms']['p99']:.1f}ms")
    print(f"recompiles after warmup: {recompiles}")
    if "plan_cache" in report:
        pcs = report["plan_cache"]
        print(f"plan cache: {pcs['hits']} hits / {pcs['misses']} misses "
              f"(hit rate {pcs['hit_rate']:.2f})")
    for r in loop.stats.finished[:1]:
        print("sample:", np.asarray(r.tokens)[:16])
    return report


if __name__ == "__main__":
    main()
