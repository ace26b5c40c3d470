"""Dense decoder-only transformer (stablelm / qwen1.5 / musicgen backbone /
internvl2 backbone families), GQA + RoPE + SwiGLU.

One parameter tree serves three entry points:

* ``forward``      — packed stream layout ``[F, T]`` (train / prefill);
  attention is pluggable (``attn_fn``) so the same code runs dense oracle
  attention (smoke tests), distributed FCP attention, or the paper's
  baselines — the transparency property of §4.3.
* ``decode_step``  — one-token decode against (possibly CP-sharded) KV
  caches; cache read/update are pluggable for the same reason.

Layers are stacked and scanned (one trace per model, not per layer) with
optional remat — required for 80-layer configs to compile quickly and for
activation memory at 512 chips.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from . import layers as L
from .moe import init_moe_ffn, moe_ffn


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def init_params(cfg: ModelConfig, key: jax.Array, tp: int = 1):
    nh, nkv = cfg.padded_heads(tp)
    vpad = cfg.padded_vocab(tp)
    d, dh, ff = cfg.d_model, cfg.head_dim, cfg.d_ff
    dt = _dt(cfg)
    ks = jax.random.split(key, 16)
    s_emb, s_d, s_ff = d ** -0.5, d ** -0.5, ff ** -0.5 if ff else 1.0

    def zeros_pad(w, axis, true_n, pad_n):
        """zero the padded tail along `axis` (exactness of head padding)."""
        if true_n == pad_n:
            return w
        idx = [slice(None)] * w.ndim
        idx[axis] = slice(true_n, None)
        return w.at[tuple(idx)].set(0.0)

    lyr = {
        "ln1": jnp.ones((cfg.n_layers, d), dt),
        "ln2": jnp.ones((cfg.n_layers, d), dt),
        "wq": zeros_pad(L.normal(ks[0], (cfg.n_layers, d, nh, dh), s_d, dt),
                        2, cfg.n_heads, nh),
        "wk": L.normal(ks[1], (cfg.n_layers, d, nkv, dh), s_d, dt),
        "wv": L.normal(ks[2], (cfg.n_layers, d, nkv, dh), s_d, dt),
        "wo": zeros_pad(L.normal(ks[3], (cfg.n_layers, nh, dh, d),
                                 (nh * dh) ** -0.5, dt), 1, cfg.n_heads, nh),
    }
    if cfg.qkv_bias:
        lyr["bq"] = jnp.zeros((cfg.n_layers, nh, dh), dt)
        lyr["bk"] = jnp.zeros((cfg.n_layers, nkv, dh), dt)
        lyr["bv"] = jnp.zeros((cfg.n_layers, nkv, dh), dt)
    if cfg.n_experts:
        lyr.update(init_moe_ffn(cfg, ks[4], tp))
    else:
        lyr["wi"] = L.normal(ks[5], (cfg.n_layers, d, ff), s_d, dt)
        lyr["wg"] = L.normal(ks[6], (cfg.n_layers, d, ff), s_d, dt)
        lyr["wdown"] = L.normal(ks[7], (cfg.n_layers, ff, d), s_ff, dt)

    params = {
        "embed": L.normal(ks[8], (vpad, d), 1.0, dt),
        "layers": lyr,
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.normal(ks[9], (d, vpad), s_emb, dt)
    if cfg.frontend_dim:
        params["frontend_proj"] = L.normal(
            ks[10], (cfg.frontend_dim, d), cfg.frontend_dim ** -0.5, dt)
    return params


def _attention_qkv(lp, cfg: ModelConfig, h, pos):
    """h: [F, T, d] -> q [F,T,H,Dh], k/v [F,T,KH,Dh] (roped)."""
    q = jnp.einsum("ftd,dhk->fthk", h, lp["wq"])
    k = jnp.einsum("ftd,dhk->fthk", h, lp["wk"])
    v = jnp.einsum("ftd,dhk->fthk", h, lp["wv"])
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = L.rope(q, pos, cfg.rope_theta)
    k = L.rope(k, pos, cfg.rope_theta)
    return q, k, v


def _layer_body(x, lp, *, cfg: ModelConfig, pos, attn_fn, layer_kind="all"):
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _attention_qkv(lp, cfg, h, pos)
    o = attn_fn(q, k, v)                                     # [F,T,H,Dh] f32
    x = x + jnp.einsum("fthk,hkd->ftd", o.astype(x.dtype), lp["wo"])
    h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        x = x + moe_ffn(h2, lp, cfg)
    else:
        x = x + L.swiglu(h2, lp["wi"], lp["wg"], lp["wdown"])
    return x


def embed_tokens(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Token embeddings; multimodal frontend STUB: ``frontend_embeds``
    [F, P, frontend_dim] are precomputed patch/frame embeddings occupying
    the first P positions of each frame where ``frontend_mask`` is set."""
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if "frontend_embeds" in batch and "frontend_proj" in params:
        fe = batch["frontend_embeds"]
        pp = fe.shape[1]
        fep = jnp.einsum("fpe,ed->fpd", fe.astype(x.dtype),
                         params["frontend_proj"])
        mask = batch["frontend_mask"][:, :pp, None]
        x = jnp.concatenate(
            [jnp.where(mask, fep, x[:, :pp]), x[:, pp:]], axis=1)
    return x


def unembed(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, params["embed"])
    return jnp.einsum("...d,dv->...v", x, params["lm_head"])


def apply_remat(body, remat):
    """remat: False | True/'dots' | 'nothing' (recompute everything —
    minimal activation memory, §Perf #2).

    'dots' saves matmul outputs and, on a layer whose FCP schedule has
    one run, the fused attention output (``ops.FUSED_ATTN_OUT``: the
    f32 ``o`` and ``lse`` accumulators, ``(slots+1)·H·bs·(D+1)·4`` bytes
    a layer), so the backward launches no second forward kernel."""
    if not remat:
        return body
    if remat == "nothing":
        return jax.checkpoint(body)
    cp = jax.checkpoint_policies
    return jax.checkpoint(body, policy=cp.save_from_both_policies(
        cp.dots_with_no_batch_dims_saveable,
        cp.save_only_these_names(ops.FUSED_ATTN_OUT)))


def forward(params, cfg: ModelConfig, batch: dict,
            attn_fn: Callable, remat=False,
            return_features: bool = False) -> jax.Array:
    """batch: tokens [F, T], positions [F, T] (+ frontend_*). -> logits
    (or pre-unembed features for the chunked-loss path).

    ``attn_fn`` is either one callable shared by every layer (scanned —
    one trace for the whole stack) or a per-layer sequence (models that
    interleave mask families route each layer through its mask group's
    schedule; the stack unrolls so each group's distinct
    executor/schedule closure applies to its own layers).

    A per-layer entry may be a plain callable or a duck-typed object
    carrying the layer-pipelined reshuffle protocol (``launch.train``
    builds these; ``docs/overlap.md``): optional ``enter(x, pos) ->
    (x', pos')`` moves the hidden state (and rope positions) into the
    entry's layout before the layer runs — first layer of a group —
    optional ``exit(x) -> x`` moves it back after — last layer of a
    group — and ``attn`` is the attention callable itself (defaults to
    the entry).  Layers between enter and exit run with the moved
    positions, so per-token math is untouched while per-layer Q/K/V
    reshuffles collapse into one hidden-state move per group.
    """
    x = embed_tokens(params, cfg, batch)
    pos = batch["positions"]
    if attn_fn is not None and not callable(attn_fn):
        fns = list(attn_fn)
        if len(fns) != cfg.n_layers:
            raise ValueError(
                f"per-layer attn_fn sequence has {len(fns)} entries for "
                f"{cfg.n_layers} layers")
        cur_pos = pos
        for i, fn in enumerate(fns):
            lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            enter = getattr(fn, "enter", None)
            if enter is not None:
                x, cur_pos = enter(x, pos)
            body = apply_remat(
                functools.partial(_layer_body, cfg=cfg, pos=cur_pos,
                                  attn_fn=getattr(fn, "attn", fn)), remat)
            x = body(x, lp)
            exit_fn = getattr(fn, "exit", None)
            if exit_fn is not None:
                x = exit_fn(x)
                cur_pos = pos
    else:
        body = apply_remat(
            functools.partial(_layer_body, cfg=cfg, pos=pos,
                              attn_fn=attn_fn), remat)

        def scan_fn(x, lp):
            return body(x, lp), None

        x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    if return_features:
        return x
    return unembed(params, cfg, x)


def forward_prefill(params, cfg: ModelConfig, batch: dict,
                    attn_fn: Callable, remat: bool = False):
    """Like :func:`forward` but also returns the per-layer roped K/V for
    cache construction: (logits, k [L,F,T,KH,Dh], v [L,F,T,KH,Dh])."""
    x = embed_tokens(params, cfg, batch)
    pos = batch["positions"]

    def body(x, lp):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _attention_qkv(lp, cfg, h, pos)
        o = attn_fn(q, k, v)
        x = x + jnp.einsum("fthk,hkd->ftd", o.astype(x.dtype), lp["wo"])
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            x = x + moe_ffn(h2, lp, cfg)
        else:
            x = x + L.swiglu(h2, lp["wi"], lp["wg"], lp["wdown"])
        return x, (k.astype(x.dtype), v.astype(x.dtype))

    if remat:
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    x, (ks, vs) = jax.lax.scan(lambda c, lp: body(c, lp), x,
                               params["layers"])
    return unembed(params, cfg, x), ks, vs


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, tp: int = 1):
    nh, nkv = cfg.padded_heads(tp)
    shape = (cfg.n_layers, batch, seq_len, nkv, cfg.head_dim)
    return {"k": jnp.zeros(shape, _dt(cfg)), "v": jnp.zeros(shape, _dt(cfg))}


def decode_step(params, cfg: ModelConfig, tokens, pos, cache,
                decode_attn_fn: Callable, cache_update_fn: Callable):
    """tokens: [B] int32; pos: [B] current positions; cache: pytree
    [L, B, S, KH, Dh].  Returns (logits [B, V], new cache).

    ``decode_attn_fn(q [B,H,Dh], k_cache, v_cache, lengths) -> o`` and
    ``cache_update_fn(cache_layer, new [B,KH,Dh], pos) -> cache_layer``
    abstract over dense vs CP-sharded caches.
    """
    x = jnp.take(params["embed"], tokens, axis=0)            # [B, d]
    posf = pos[:, None]                                      # [B, 1]

    def scan_fn(x, xs):
        lp, kc, vc = xs
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", h, lp["wq"])
        k = jnp.einsum("bd,dhk->bhk", h, lp["wk"])
        v = jnp.einsum("bd,dhk->bhk", h, lp["wv"])
        if "bq" in lp:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = L.rope(q[:, None], posf, cfg.rope_theta)[:, 0]
        k = L.rope(k[:, None], posf, cfg.rope_theta)[:, 0]
        kc = cache_update_fn(kc, k, pos)
        vc = cache_update_fn(vc, v, pos)
        o = decode_attn_fn(q, kc, vc, pos + 1)               # [B, H, Dh]
        x = x + jnp.einsum("bhk,hkd->bd", o.astype(x.dtype), lp["wo"])
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            x = x + moe_ffn(h2[:, None], lp, cfg)[:, 0]
        else:
            x = x + L.swiglu(h2, lp["wi"], lp["wg"], lp["wdown"])
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(scan_fn, x,
                               (params["layers"], cache["k"], cache["v"]))
    logits = unembed(params, cfg, x)
    return logits, {"k": ks, "v": vs}
