"""The dense decoder-only transformer: its sizes, its weights from the
seed, its plain float32 reference train step, and the operations and
bytes a step of it requires.

The model is the one the configuration files describe as run: RMSNorm,
rotary positions over the whole head (half-split rotation), optional
q/k/v bias added before the rotation, causal attention within each
document, SwiGLU, an untied head, and the token-mean cross entropy over
the loss mask.  A configuration file without an ``"arch"`` key is this
architecture.

Weights: the tree has the layout the trainer takes (stacked layers
under ``layers``); values are drawn here, by the benchmark, so the
reference can draw the same ones again without reading anything the
program made.  Scales follow the usual fan-in rule; biases are drawn
small and non-zero so that the bias path carries signal.

Reference: so that it fits beside nothing else on one chip it runs in
stages, each its own jitted program: the forward to the final features;
the head and loss over vocabulary slices with their gradient written by
hand; the layers' backward by autodiff with attention in query blocks;
the embedding's gradient.  The global-norm clip needs every gradient
before any update, so the head's and the layers' gradients are held and
the embedding's (the largest) is formed twice.  ``mode="fp8"`` is the
control (:mod:`chipbench.reference`); ``fault="half_batch"`` drops the
second half of the tokens from the loss and takes the mean over the
rest; ``fault="loss_altered"`` reports each step's loss 1% high, the
answer altered where the step produces it; ``fault="no_exchange"`` cuts
attention at the workers' frames of the batch (its token ids come as
``[workers, tokens_per_worker]``), as if no K/V crossed between chips.

Counts: recomputed work (rematerialisation, the kernel's own recompute)
is not counted: these are what the algorithm needs, so a share of a
peak says how close the implementation comes to it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import causal_pairs
from chipbench.reference import (F32, HI, Hyper, Readings, adam, adam_embed,
                                 as_f32, change_norms, derive, einsum,
                                 embed_sumsq, fake_quant, leaf_norms,
                                 seed_key, sumsq)

Q_BLOCK = 512          # attention query block
Q_KEYS = 4 << 20       # most (query, key) pairs of one block, per head
V_CAP = 8192           # largest vocabulary slice of the head


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of a dense decoder-only transformer as the benchmark runs
    it: RMSNorm, rotary positions over the whole head, optional q/k/v
    bias, SwiGLU, untied head."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    param_dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if float(c.get("partial_rotary_factor", 1.0)) != 1.0:
            raise ValueError("the program rotates the whole head: "
                             "partial_rotary_factor must be 1.0 as run")
        if c.get("tie_word_embeddings", False):
            raise ValueError("tied embeddings are not run here")
        heads = int(c["num_attention_heads"])
        return cls(
            n_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]),
            n_heads=heads,
            n_kv_heads=int(c.get("num_key_value_heads", heads)),
            head_dim=int(c.get("head_dim",
                               int(c["hidden_size"]) // heads)),
            d_ff=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]),
            qkv_bias=bool(c.get("use_qkv_bias", c.get("qkv_bias", False))),
            rope_theta=float(c.get("rope_theta", 10000.0)),
            norm_eps=float(c.get("rms_norm_eps",
                                 c.get("layer_norm_eps", 1e-5))),
            param_dtype=str(c.get("param_dtype", "bfloat16")))

    # parameter counts -----------------------------------------------------

    def layer_matmul_params(self) -> int:
        """Weights of one layer that enter a matrix multiplication."""
        d, dh = self.d_model, self.head_dim
        attn = d * dh * (2 * self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * d * self.d_ff

    def matmul_params(self) -> int:
        """Every layer's matmul weights plus the output head; the
        embedding lookup is a gather and is left out."""
        return (self.n_layers * self.layer_matmul_params()
                + self.d_model * self.vocab)


def sizes(config: dict) -> Dims:
    """The configuration file read into the sizes everything here takes
    (hashable: the jitted reference takes them as a static argument)."""
    return Dims.from_config(config)


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

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


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: [T, H, D]; rotate pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = pos.astype(F32)[:, None] * freq                    # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, seg, mode: str):
    """Causal attention within documents, in query blocks (each block
    recomputed under autodiff) of at most ``Q_KEYS`` scores a head, so
    that a longer batch runs in smaller blocks.  q: [T, H, D]; k, v:
    [T, KH, D]."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    blk = min(Q_BLOCK, t, max(Q_KEYS // t, 1))
    idx = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qb, sb, ib = args
        s = einsum("bhd,thd->hbt", qb, k, mode) * d ** -0.5
        ok = (sb[:, None] == seg[None, :]) & (idx[None, :] <= ib[:, None])
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        p = p / jnp.sum(p, -1, keepdims=True)
        return einsum("hbt,thd->bhd", p, v, mode)

    out = jax.lax.map(one, (q.reshape(t // blk, blk, h, d),
                            seg.reshape(t // blk, blk),
                            idx.reshape(t // blk, blk)))
    return out.reshape(t, h, d)


def layers_forward(lyr, x, pos, seg, dims: Dims, mode: str):
    """The layer stack on x [T, d] (float32 weights)."""
    for i in range(dims.n_layers):
        lp = {n: a[i] for n, a in lyr.items()}
        h = rms_norm(x, lp["ln1"], dims.norm_eps)
        q = einsum("td,dhk->thk", h, lp["wq"], mode)
        k = einsum("td,dhk->thk", h, lp["wk"], mode)
        v = einsum("td,dhk->thk", h, lp["wv"], mode)
        if dims.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = rope(q, pos, dims.rope_theta)
        k = rope(k, pos, dims.rope_theta)
        o = attention(q, k, v, seg, mode)
        x = x + einsum("thk,hkd->td", o, lp["wo"], mode)
        h2 = rms_norm(x, lp["ln2"], dims.norm_eps)
        a = einsum("td,df->tf", h2, lp["wi"], mode)
        g = einsum("td,df->tf", h2, lp["wg"], mode)
        x = x + einsum("tf,fd->td", jax.nn.silu(g) * a, lp["wdown"], mode)
    return x


def _slice(n: int, cap: int) -> int:
    return max(c for c in range(1, min(n, cap) + 1) if n % c == 0)


def head_and_loss(fn, w, feats, labels, mask, dims: Dims, mode: str):
    """Loss, the head's gradient [d, V] and the gradients of the final
    norm and the features, over vocabulary slices of the head.

    logits = rmsnorm(feats) @ w; loss = sum(mask * (lse - logit[label]))
    / sum(mask); d logits = mask / sum(mask) * (softmax - onehot)."""
    h, norm_vjp = jax.vjp(lambda f, g: rms_norm(f, g, dims.norm_eps),
                          feats, fn)
    t, v = labels.shape[0], dims.vocab
    vs = _slice(v, V_CAP)
    n = v // vs
    q8 = mode == "fp8"
    hq = fake_quant(h, jnp.float8_e4m3fn) if q8 else h

    def logits_of(c):
        wc = jax.lax.dynamic_slice_in_dim(w, c * vs, vs, 1).astype(F32)
        if q8:
            wc = fake_quant(wc, jnp.float8_e4m3fn)
        return jnp.dot(hq, wc, precision=HI), wc

    def lse_step(c, carry):
        mx, se, gold = carry
        z, _ = logits_of(c)
        m2 = jnp.maximum(mx, jnp.max(z, -1))
        se = se * jnp.exp(mx - m2) + jnp.sum(jnp.exp(z - m2[:, None]), -1)
        rel = labels - c * vs
        inside = (rel >= 0) & (rel < vs)
        zl = jnp.take_along_axis(z, jnp.clip(rel, 0, vs - 1)[:, None],
                                 1)[:, 0]
        return m2, se, gold + jnp.where(inside, zl, 0.0)

    zeros = jnp.zeros((t,), F32)
    mx, se, gold = jax.lax.fori_loop(
        0, n, lse_step, (jnp.full((t,), -jnp.inf, F32), zeros, zeros))
    lse = mx + jnp.log(se)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(mask * (lse - gold)) / denom
    wt = mask / denom

    def grad_step(c, carry):
        gw, dh = carry
        z, wc = logits_of(c)
        p = jnp.exp(z - lse[:, None])
        hot = (labels[:, None] == (c * vs + jnp.arange(vs))[None, :])
        dz = (p - hot.astype(F32)) * wt[:, None]
        if q8:
            dz = fake_quant(dz, jnp.float8_e5m2)
        gw = jax.lax.dynamic_update_slice_in_dim(
            gw, jnp.dot(hq.T, dz, precision=HI), c * vs, 1)
        return gw, dh + jnp.dot(dz, wc.T, precision=HI)

    gw, dh = jax.lax.fori_loop(
        0, n, grad_step, (jnp.zeros((dims.d_model, v), F32),
                          jnp.zeros_like(h)))
    d_feats, g_fn = norm_vjp(dh)
    return loss, gw, g_fn, d_feats


# --------------------------------------------------------------------------
# the staged train step
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _features(embed, lyr, b, dims, mode):
    x0 = jnp.take(embed, b["tokens"], axis=0).astype(F32)
    return layers_forward(as_f32(lyr), x0, b["pos"], b["seg"], dims, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _head(fn, w, feats, b, dims, mode):
    return head_and_loss(fn.astype(F32), w, feats, b["labels"], b["mask"],
                         dims, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layers_backward(embed, lyr, d_feats, b, dims, mode):
    x0 = jnp.take(embed, b["tokens"], axis=0).astype(F32)
    _, vjp = jax.vjp(
        lambda lp, x: layers_forward(lp, x, b["pos"], b["seg"], dims, mode),
        as_f32(lyr), x0)
    return vjp(d_feats)




def loss_and_grads(dims: Dims, p: dict, tokens, seqlens,
                   mode: str = "f32"):
    """One step's loss and every gradient as one tree (the embedding's
    dense), for tests at small sizes."""
    b = jax.device_put(derive(tokens, seqlens))
    with jax.default_matmul_precision("highest"):
        feats = _features(p["embed"], p["layers"], b, dims, mode)
        loss, g_head, g_fn, d_feats = _head(p["final_norm"], p["lm_head"],
                                            feats, b, dims, mode)
        g_lyr, d_x0 = _layers_backward(p["embed"], p["layers"], d_feats, b,
                                       dims, mode)
    g_emb = jnp.zeros(p["embed"].shape, F32).at[b["tokens"]].add(d_x0)
    return loss, {"embed": g_emb, "final_norm": g_fn, "layers": g_lyr,
                  "lm_head": g_head}


def run(dims: Dims, hp: Hyper, seed: int, batches, mode: str = "f32",
        fault: str | None = None) -> Readings:
    """The first ``len(batches)`` steps from the seed's weights.
    ``batches``: ``(token ids, document lengths)`` per step."""
    if mode not in ("f32", "fp8"):
        raise ValueError(f"unknown mode {mode!r}")
    make = maker(dims)
    p = make(seed_key(seed))
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p)
    losses, grad_norms = [], None
    small = ("final_norm", "lm_head", "layers")
    with jax.default_matmul_precision("highest"):
        for step, (tokens, seqlens) in enumerate(batches):
            b = derive(tokens, seqlens)
            if fault == "half_batch":
                b["mask"] = b["mask"] * (np.arange(b["mask"].size)
                                         < b["mask"].size // 2)
            elif fault == "no_exchange":
                workers = np.shape(tokens)[0] if np.ndim(tokens) > 1 else 1
                frame = np.arange(b["seg"].size) * workers // b["seg"].size
                b["seg"] = (b["seg"] * workers + frame).astype(np.int32)
            elif fault not in (None, "loss_altered"):
                raise ValueError(f"unknown fault {fault!r}")
            b = jax.device_put(b)
            feats = _features(p["embed"], p["layers"], b, dims, mode)
            loss, g_head, g_fn, d_feats = _head(p["final_norm"],
                                                p["lm_head"], feats, b,
                                                dims, mode)
            del feats
            g_lyr, d_x0 = _layers_backward(p["embed"], p["layers"],
                                           d_feats, b, dims, mode)
            grads = {"final_norm": g_fn, "lm_head": g_head,
                     "layers": g_lyr}
            gnorm = float(jnp.sqrt(sumsq(grads)
                                   + embed_sumsq(b["tokens"], d_x0)))
            scale = min(1.0, hp.grad_clip / max(gnorm, 1e-12))
            lr, t = hp.lr_at(step), float(step + 1)
            for k in small:
                p[k], m[k], v[k] = adam(p[k], grads.pop(k), m[k], v[k],
                                         lr, t, scale, hp)
            p["embed"], m["embed"], v["embed"] = adam_embed(
                b["tokens"], p["embed"], m["embed"], v["embed"], d_x0, lr,
                t, scale, hp)
            losses.append(float(loss) * (1.01 if fault == "loss_altered"
                                         else 1.0))
            if step == 0:
                grad_norms = leaf_norms(m, 1.0 / (1.0 - hp.b1))
        update_norms = change_norms(p, make(seed_key(seed)))
    return Readings(losses=losses, grad_norms=grad_norms,
                    update_norms=update_norms)


# --------------------------------------------------------------------------
# operations and bytes of a train step
# --------------------------------------------------------------------------

def attention_flops(dims: Dims, seqlens: Sequence[int]) -> int:
    """Causal attention, forward and backward, all layers: 4·dh per kept
    pair and head forward (QK^T and PV), twice that backward."""
    return (12 * dims.n_heads * dims.head_dim * causal_pairs(seqlens)
            * dims.n_layers)


def model_flops(dims: Dims, seqlens: Sequence[int]) -> int:
    """One train step: 6 x matmul parameters x tokens, plus attention."""
    tokens = sum(int(n) for n in seqlens)
    return 6 * dims.matmul_params() * tokens + attention_flops(dims,
                                                               seqlens)


def attention_bytes(dims: Dims, seqlens: Sequence[int],
                    itemsize: int = 2) -> int:
    """Least HBM traffic of attention, forward and backward, all layers:
    forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO
    and writes dQ, dK, dV; every tensor touched once."""
    tokens = sum(int(n) for n in seqlens)
    q = dims.n_heads * dims.head_dim * tokens
    kv = dims.n_kv_heads * dims.head_dim * tokens
    fwd = q + 2 * kv + q
    bwd = (q + 2 * kv + q + q) + (q + 2 * kv)
    return (fwd + bwd) * itemsize * dims.n_layers


def attention_least_seconds(dims: Dims, seqlens: Sequence[int],
                            peak: dict) -> tuple[float, str]:
    """Least time the chip could spend on the attention work, and which
    roof bounds it (``"compute"`` or ``"memory"``)."""
    t_c = attention_flops(dims, seqlens) / peak["bf16_flops_per_s"]
    t_m = attention_bytes(dims, seqlens) / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
