"""Plain float32 reference of the trainer's step: forward, loss,
gradients and the AdamW update, in ``jax.numpy``.

It imports nothing of the program and reads nothing the program made:
the weights come from the seed (:mod:`chipbench.weights`), and labels,
positions, segments and the loss mask are derived here from a batch's
token ids and document lengths.  The model is the one the configuration
files describe as run: RMSNorm, rotary positions over the whole head
(half-split rotation), optional q/k/v bias added before the rotation,
causal attention within each document, SwiGLU, an untied head, and the
token-mean cross entropy over the loss mask.  Weights are stored in the
configuration's ``param_dtype`` (bfloat16) and AdamW's moments in
float32, as the configuration states; all arithmetic is float32 with
matmuls at ``Precision.HIGHEST``.

So that it fits beside nothing else on one chip it runs in stages, each
its own jitted program: the forward to the final features; the head and
loss over vocabulary slices with their gradient written by hand; the
layers' backward by autodiff with attention in query blocks; the
embedding's gradient.  The global-norm clip needs every gradient before
any update, so the head's and the layers' gradients are held and the
embedding's (the largest) is formed twice.

``mode="fp8"`` is the control: every matmul takes float8 operands (e4m3
forward, e5m2 for the gradient flowing back, one scale per tensor or
head slice), the precision one step below the configuration's bfloat16.
``fault="half_batch"`` drops the second half of the tokens from the loss
and takes the mean over the rest; ``fault="loss_altered"`` reports each
step's loss 1% high, the answer altered where the step produces it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .model_cfg import Dims
from .weights import maker, seed_key

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # attention query block
V_CAP = 8192           # largest vocabulary slice of the head


@dataclasses.dataclass(frozen=True)
class Hyper:
    lr: float
    warmup_steps: int
    total_steps: int
    weight_decay: float
    grad_clip: float
    b1: float
    b2: float
    eps: float = 1e-8
    final_frac: float = 0.1

    @classmethod
    def from_traffic(cls, traffic: dict) -> "Hyper":
        t = traffic["train"]
        return cls(lr=float(t["lr"]), warmup_steps=int(t["warmup_steps"]),
                   total_steps=int(t["total_steps"]),
                   weight_decay=float(t["weight_decay"]),
                   grad_clip=float(t["grad_clip"]), b1=float(t["b1"]),
                   b2=float(t["b2"]))

    def lr_at(self, step: int) -> float:
        """Linear warm-up to ``lr`` over ``warmup_steps``, then a cosine
        to ``final_frac`` of it at ``total_steps``."""
        if step < self.warmup_steps:
            return self.lr * step / max(self.warmup_steps, 1)
        t = min(max((step - self.warmup_steps)
                    / max(self.total_steps - self.warmup_steps, 1), 0.0),
                1.0)
        return self.lr * (self.final_frac + (1 - self.final_frac) * 0.5
                          * (1 + np.cos(np.pi * t)))


@dataclasses.dataclass
class Readings:
    """What the comparison reads from a run of the first three steps:
    each step's loss, each leaf's norm of the first gradient as AdamW
    got it (from its first moment after one step), and each leaf's norm
    of the change of the weights over the three steps."""
    losses: list
    grad_norms: dict
    update_norms: dict


# --------------------------------------------------------------------------
# matmuls in the stated precision, or float8 for the control
# --------------------------------------------------------------------------

def _fake_quant(x, dtype):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(F32) * s


def _einsum_fp8(spec: str, a, b):
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, _fake_quant(a, jnp.float8_e4m3fn),
                          _fake_quant(b, jnp.float8_e4m3fn), precision=HI)

    def fwd(a, b):
        qa = _fake_quant(a, jnp.float8_e4m3fn)
        qb = _fake_quant(b, jnp.float8_e4m3fn)
        return jnp.einsum(spec, qa, qb, precision=HI), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(
            lambda x, y: jnp.einsum(spec, x, y, precision=HI), *res)
        return vjp(_fake_quant(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f(a, b)


def einsum(spec: str, a, b, mode: str):
    if mode == "fp8":
        return _einsum_fp8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HI)


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
    recomputed under autodiff).  q: [T, H, D]; k, v: [T, KH, D]."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    blk = min(Q_BLOCK, t)
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
    hq = _fake_quant(h, jnp.float8_e4m3fn) if q8 else h

    def logits_of(c):
        wc = jax.lax.dynamic_slice_in_dim(w, c * vs, vs, 1).astype(F32)
        if q8:
            wc = _fake_quant(wc, jnp.float8_e4m3fn)
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
            dz = _fake_quant(dz, jnp.float8_e5m2)
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

def derive(tokens: np.ndarray, seqlens) -> dict:
    """Labels, positions, segment ids and loss mask of a packed stream:
    each document predicts its next token, and its last token none."""
    tokens = np.asarray(tokens).reshape(-1).astype(np.int32)
    n = tokens.shape[0]
    if sum(int(x) for x in seqlens) != n:
        raise ValueError(f"documents hold {sum(seqlens)} tokens, the "
                         f"batch {n}")
    labels = np.zeros(n, np.int32)
    mask = np.zeros(n, np.float32)
    off = 0
    for length in seqlens:
        labels[off:off + length - 1] = tokens[off + 1:off + length]
        mask[off:off + length - 1] = 1.0
        off += length
    seg = np.repeat(np.arange(len(seqlens), dtype=np.int32), seqlens)
    pos = np.concatenate([np.arange(x, dtype=np.int32) for x in seqlens])
    return {"tokens": tokens, "labels": labels, "mask": mask, "seg": seg,
            "pos": pos}


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _features(embed, lyr, b, dims, mode):
    x0 = jnp.take(embed, b["tokens"], axis=0).astype(F32)
    return layers_forward(_f32(lyr), x0, b["pos"], b["seg"], dims, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _head(fn, w, feats, b, dims, mode):
    return head_and_loss(fn.astype(F32), w, feats, b["labels"], b["mask"],
                         dims, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layers_backward(embed, lyr, d_feats, b, dims, mode):
    x0 = jnp.take(embed, b["tokens"], axis=0).astype(F32)
    _, vjp = jax.vjp(
        lambda lp, x: layers_forward(lp, x, b["pos"], b["seg"], dims, mode),
        _f32(lyr), x0)
    return vjp(d_feats)


@jax.jit
def _embed_sumsq(tokens, d_x0):
    """Squared norm of the embedding's gradient (rows of repeated tokens
    summed) without forming the [V, d] matrix."""
    _, inv = jnp.unique(tokens, return_inverse=True, size=tokens.shape[0],
                        fill_value=0)
    rows = jax.ops.segment_sum(d_x0, inv.reshape(-1),
                               num_segments=tokens.shape[0])
    return jnp.sum(rows * rows)


@jax.jit
def sumsq(tree):
    return sum(jnp.sum(jnp.square(x.astype(F32)))
               for x in jax.tree.leaves(tree))


def _adam_leaf(p, g, m, v, lr, t, scale, hp: Hyper):
    g = g * scale
    m = hp.b1 * m + (1 - hp.b1) * g
    v = hp.b2 * v + (1 - hp.b2) * g * g
    mh = m / (1 - hp.b1 ** t)
    vh = v / (1 - hp.b2 ** t)
    p32 = p.astype(F32)
    p32 = p32 - lr * (mh / (jnp.sqrt(vh) + hp.eps) + hp.weight_decay * p32)
    return p32.astype(p.dtype), m, v


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(0, 2, 3))
def _adam(p, g, m, v, lr, t, scale, hp):
    out = jax.tree.map(
        lambda *a: _adam_leaf(*a, lr, t, scale, hp), p, g, m, v)
    first = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                   is_leaf=lambda o: isinstance(o, tuple))
    return first(0), first(1), first(2)


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(1, 2, 3))
def _adam_embed(tokens, p, m, v, d_x0, lr, t, scale, hp):
    g = jnp.zeros(p.shape, F32).at[tokens].add(d_x0)
    return _adam_leaf(p, g, m, v, lr, t, scale, hp)


def leaf_names(tree) -> list[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def leaf_norms(tree, scale: float = 1.0) -> dict:
    vals = jax.device_get(_norms(tree))
    return {n: float(x) * scale for n, x in zip(leaf_names(tree), vals)}


def change_norms(after, before) -> dict:
    vals = jax.device_get(_diff_norms(after, before))
    return {n: float(x) for n, x in zip(leaf_names(after), vals)}


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
                                   + _embed_sumsq(b["tokens"], d_x0)))
            scale = min(1.0, hp.grad_clip / max(gnorm, 1e-12))
            lr, t = hp.lr_at(step), float(step + 1)
            for k in small:
                p[k], m[k], v[k] = _adam(p[k], grads.pop(k), m[k], v[k],
                                         lr, t, scale, hp)
            p["embed"], m["embed"], v["embed"] = _adam_embed(
                b["tokens"], p["embed"], m["embed"], v["embed"], d_x0, lr,
                t, scale, hp)
            losses.append(float(loss) * (1.01 if fault == "loss_altered"
                                         else 1.0))
            if step == 0:
                grad_norms = leaf_norms(m, 1.0 / (1.0 - hp.b1))
        update_norms = change_norms(p, make(seed_key(seed)))
    return Readings(losses=losses, grad_norms=grad_norms,
                    update_norms=update_norms)
