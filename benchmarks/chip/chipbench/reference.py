"""What every architecture's plain float32 reference shares: the seed's
key, the hyper-parameters, the readings the comparison takes, matmuls in
the stated precision or in float8 for the control, the packed batch's
labels and masks, AdamW, and leaf norms.

It imports nothing of the program and reads nothing the program made.
The model itself, its staged train step and its faults are the
architecture's (``archs/<name>.py``, :mod:`chipbench.archs`).  Weights
are stored in the configuration's ``param_dtype`` (bfloat16) and AdamW's
moments in float32, as the configuration states; all arithmetic is
float32 with matmuls at ``Precision.HIGHEST``.

``mode="fp8"`` is the control: every matmul takes float8 operands (e4m3
forward, e5m2 for the gradient flowing back, one scale per tensor or
head slice), the precision one step below the configuration's bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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

def fake_quant(x, dtype):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(F32) * s


def _einsum_fp8(spec: str, a, b):
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, fake_quant(a, jnp.float8_e4m3fn),
                          fake_quant(b, jnp.float8_e4m3fn), precision=HI)

    def fwd(a, b):
        qa = fake_quant(a, jnp.float8_e4m3fn)
        qb = fake_quant(b, jnp.float8_e4m3fn)
        return jnp.einsum(spec, qa, qb, precision=HI), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(
            lambda x, y: jnp.einsum(spec, x, y, precision=HI), *res)
        return vjp(fake_quant(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f(a, b)


def einsum(spec: str, a, b, mode: str):
    if mode == "fp8":
        return _einsum_fp8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HI)


# --------------------------------------------------------------------------
# the packed batch, AdamW and leaf norms
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


def as_f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


@jax.jit
def embed_sumsq(tokens, d_x0):
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
def adam(p, g, m, v, lr, t, scale, hp):
    out = jax.tree.map(
        lambda *a: _adam_leaf(*a, lr, t, scale, hp), p, g, m, v)
    first = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                   is_leaf=lambda o: isinstance(o, tuple))
    return first(0), first(1), first(2)


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(1, 2, 3))
def adam_embed(tokens, p, m, v, d_x0, lr, t, scale, hp):
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
