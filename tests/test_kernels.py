"""Pallas kernel validation: shape/dtype sweeps against the jnp oracle
(interpret mode executes the kernel body on CPU)."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # minimal install: skip @given only
    from _hypothesis_fallback import given, settings, st

from _jaxpr import count_kernel_calls
from repro import masks
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.models.transformer import apply_remat


def _make_inputs(rng, h, kh, sq, sk, d, dtype, n_docs=3, pad_frac=0.1):
    q = jnp.asarray(rng.normal(size=(h, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(kh, sk, d)), dtype)
    v = jnp.asarray(rng.normal(size=(kh, sk, d)), dtype)

    def meta(n):
        npad = int(n * pad_frac)
        body = n - npad
        cuts = np.sort(rng.choice(np.arange(1, body), size=n_docs - 1,
                                  replace=False)) if body > n_docs else []
        seg = np.zeros(n, np.int32)
        pos = np.zeros(n, np.int32)
        lo = 0
        for i, hi in enumerate(list(cuts) + [body]):
            seg[lo:hi] = i
            pos[lo:hi] = np.arange(hi - lo)
            lo = hi
        seg[body:] = -1
        return jnp.asarray(seg), jnp.asarray(pos)

    # q and kv share the document structure on a common stream: make kv a
    # prefix-superset stream so causal masking is meaningful
    seg_k, pos_k = meta(sk)
    seg_q, pos_q = meta(sq)
    return q, k, v, seg_q, pos_q, seg_k, pos_k


SHAPES = [
    # (h, kh, sq, sk, d, block_q, block_k)
    (4, 4, 128, 128, 64, 128, 128),
    (4, 2, 256, 512, 64, 128, 128),
    (8, 1, 128, 384, 128, 128, 128),
    (2, 2, 384, 128, 32, 128, 128),
    (6, 2, 256, 256, 80, 256, 128),    # non-pow2 head dim (internvl-style)
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_fwd_matches_oracle(shape, dtype, causal):
    h, kh, sq, sk, d, bq, bk = shape
    rng = np.random.default_rng(hash((shape, str(dtype), causal)) % 2 ** 31)
    q, k, v, sq_, pq_, sk_, pk_ = _make_inputs(rng, h, kh, sq, sk, d, dtype)
    o_ref, lse_ref = ref.reference_attention(q, k, v, sq_, pq_, sk_, pk_,
                                             causal)
    o, lse = fa.flash_attention_fwd(q, k, v, sq_, pq_, sk_, pk_,
                                    mask=causal, block_q=bq, block_k=bk,
                                    interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=tol, rtol=tol)
    live = np.asarray(lse_ref) > -1e29
    np.testing.assert_allclose(np.asarray(lse)[live],
                               np.asarray(lse_ref)[live], atol=tol, rtol=tol)


def test_fully_masked_rows_are_zero():
    rng = np.random.default_rng(0)
    h, kh, s, d = 2, 2, 128, 32
    q = jnp.asarray(rng.normal(size=(h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(kh, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(kh, s, d)), jnp.float32)
    seg_q = jnp.full((s,), 7, jnp.int32)      # no kv token matches
    seg_k = jnp.zeros((s,), jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)
    o, lse = fa.flash_attention_fwd(q, k, v, seg_q, pos, seg_k, pos,
                                    mask=True, block_q=128, block_k=128,
                                    interpret=True)
    assert np.all(np.asarray(o) == 0.0)
    assert np.all(np.asarray(lse) <= -1e29)


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_bwd_matches_autodiff(shape):
    h, kh, sq, sk, d, bq, bk = shape
    rng = np.random.default_rng(99)
    q, k, v, sq_, pq_, sk_, pk_ = _make_inputs(
        rng, h, kh, sq, sk, d, jnp.float32)

    def loss_ref(q, k, v):
        o, lse = ref.reference_attention(q, k, v, sq_, pq_, sk_, pk_, True)
        # include lse in the loss so dlse != 0 (the FCP merge case)
        return jnp.sum(o * o) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0))

    def loss_pl(q, k, v):
        o, lse = ops.block_attention(q, k, v, sq_, pq_, sk_, pk_,
                                     mask=True, impl="pallas",
                                     block_q=bq, block_k=bk, interpret=True)
        return jnp.sum(o * o) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_pl, g_ref, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


def test_merge_partials_equals_joint():
    """Splitting KV into parts and merging == attention over the union."""
    rng = np.random.default_rng(1)
    h, kh, sq, sk, d = 4, 2, 128, 512, 64
    q, k, v, sq_, pq_, sk_, pk_ = _make_inputs(
        rng, h, kh, sq, sk, d, jnp.float32)
    o_all, lse_all = ref.reference_attention(q, k, v, sq_, pq_, sk_, pk_,
                                             True)
    cut = 256
    o1, l1 = ref.reference_attention(q, k[:, :cut], v[:, :cut], sq_, pq_,
                                     sk_[:cut], pk_[:cut], True)
    o2, l2 = ref.reference_attention(q, k[:, cut:], v[:, cut:], sq_, pq_,
                                     sk_[cut:], pk_[cut:], True)
    o, lse = ref.merge_partials(o1, l1, o2, l2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_all), atol=1e-5)
    live = np.asarray(lse_all) > -1e29
    np.testing.assert_allclose(np.asarray(lse)[live],
                               np.asarray(lse_all)[live], atol=1e-5)


@given(st.integers(0, 10 ** 6), st.sampled_from([1, 2, 4]),
       st.sampled_from([128, 256]), st.sampled_from([2, 3, 5]))
@settings(max_examples=10, deadline=None)
def test_merge_property_random_partitions(seed, parts_pow, sk, n_docs):
    """Property: any KV partition merges to the dense result."""
    rng = np.random.default_rng(seed)
    h, kh, sq, d = 2, 2, 64, 32
    q, k, v, sq_, pq_, sk_, pk_ = _make_inputs(rng, h, kh, sq, sk, d,
                                               jnp.float32, n_docs=n_docs)
    o_all, lse_all = ref.reference_attention(q, k, v, sq_, pq_, sk_, pk_,
                                             True)
    n_parts = parts_pow
    cuts = sorted(rng.choice(np.arange(1, sk), size=n_parts - 1,
                             replace=False).tolist()) if n_parts > 1 else []
    bounds = [0] + list(cuts) + [sk]
    o = jnp.zeros_like(o_all)
    lse = jnp.full(lse_all.shape, ref.NEG_INF, jnp.float32)
    order = rng.permutation(len(bounds) - 1)     # merge in random order
    for pi in order:
        lo, hi = bounds[pi], bounds[pi + 1]
        oi, li = ref.reference_attention(q, k[:, lo:hi], v[:, lo:hi], sq_,
                                         pq_, sk_[lo:hi], pk_[lo:hi], True)
        o, lse = ref.merge_partials(o, lse, oi, li)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_all), atol=1e-5)


def test_chunked_equals_dense_sweep():
    rng = np.random.default_rng(5)
    for sk in (130, 512, 700):
        q, k, v, sq_, pq_, sk_, pk_ = _make_inputs(
            rng, 2, 1, 64, sk, 32, jnp.float32)
        o_d, _ = ref.reference_attention(q, k, v, sq_, pq_, sk_, pk_, True)
        o_c, _ = ref.chunked_attention(q, k, v, sq_, pq_, sk_, pk_, True,
                                       chunk=128)
        np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_d),
                                   atol=1e-5)


# --------------------------------------------------------------------------
# fused schedule-driven kernels (interpret mode)
# --------------------------------------------------------------------------

def _fused_setup(rng, SL=4, H=4, KH=2, bs=128, d=32, EX=6):
    """Executor-shaped buffers + a q-sorted run over one document stream."""
    qs = jnp.asarray(rng.normal(size=(SL, H, bs, d)), jnp.float32)
    kxt = jnp.asarray(rng.normal(size=(EX, KH, bs, d)), jnp.float32)
    vxt = jnp.asarray(rng.normal(size=(EX, KH, bs, d)), jnp.float32)
    q_seg = jnp.zeros((SL, bs), jnp.int32).at[SL - 1].set(-1)  # trash slot
    q_pos = (jnp.arange(bs, dtype=jnp.int32)[None]
             + jnp.arange(SL, dtype=jnp.int32)[:, None] * bs)
    kv_seg = jnp.zeros((EX, bs), jnp.int32).at[EX - 1].set(-1)
    kv_pos = (jnp.arange(bs, dtype=jnp.int32)[None]
              + jnp.arange(EX, dtype=jnp.int32)[:, None] * bs)
    # this run — slot 0: kv {0}; slot 1: kv {1}; slot 2: kv {0, 1, 2};
    # plus a trash step.  Shared kv rows exercise the dkv revisit
    # accumulation; slot 1 additionally carries kv row 0 in from a
    # "previous run" through the incoming accumulator.
    step_q = jnp.asarray([0, 1, 2, 2, 2, SL - 1], jnp.int32)
    step_kv = jnp.asarray([0, 1, 0, 1, 2, EX - 1], jnp.int32)
    order = np.lexsort((np.asarray(step_q), np.asarray(step_kv)))
    tabs = dict(step_q=step_q, step_kv=step_kv, q_seg=q_seg, q_pos=q_pos,
                k_seg=kv_seg[step_kv], k_pos=kv_pos[step_kv],
                bwd_q=step_q[order], bwd_kv=step_kv[order],
                k_seg_b=kv_seg[step_kv[order]],
                k_pos_b=kv_pos[step_kv[order]])
    acc_o = jnp.zeros((SL, H, bs, d), jnp.float32)
    acc_lse = jnp.full((SL, H, bs), ref.NEG_INF, jnp.float32)
    o_prev, l_prev = ref.reference_attention(
        qs[1], kxt[0], vxt[0], q_seg[1], q_pos[1], kv_seg[0], kv_pos[0],
        True)
    acc_o = acc_o.at[1].set(o_prev)
    acc_lse = acc_lse.at[1].set(l_prev)
    return qs, kxt, vxt, tabs, acc_o, acc_lse, (q_seg, q_pos, kv_seg, kv_pos)


@pytest.mark.parametrize("block", [64, 128])
def test_fused_fwd_matches_reference(block):
    """One fused launch == per-slot reference attention over the union of
    each slot's KV blocks merged with the incoming accumulator."""
    rng = np.random.default_rng(11)
    qs, kxt, vxt, tabs, acc_o, acc_lse, meta = _fused_setup(rng)
    q_seg, q_pos, kv_seg, kv_pos = meta
    o2, l2 = ops.fused_run_attention(
        qs, kxt, vxt, acc_o, acc_lse, tabs, mask=True, impl="pallas",
        block_q=block, block_k=block, interpret=True)
    consumed = {0: [0], 1: [0, 1], 2: [0, 1, 2]}     # slot -> kv rows
    for slot, rows in consumed.items():
        kk = jnp.concatenate([kxt[r] for r in rows], axis=1)
        vv = jnp.concatenate([vxt[r] for r in rows], axis=1)
        sk = jnp.concatenate([kv_seg[r] for r in rows])
        pk = jnp.concatenate([kv_pos[r] for r in rows])
        o_ref, lse_ref = ref.reference_attention(
            qs[slot], kk, vv, q_seg[slot], q_pos[slot], sk, pk, True)
        np.testing.assert_allclose(np.asarray(o2[slot]), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)
        live = np.asarray(lse_ref) > -1e29
        np.testing.assert_allclose(np.asarray(l2[slot])[live],
                                   np.asarray(lse_ref)[live],
                                   atol=2e-5, rtol=2e-5)
    # untouched slots pass through unchanged (gradient path across runs)
    np.testing.assert_array_equal(np.asarray(o2[3]), np.asarray(acc_o[3]))
    np.testing.assert_array_equal(np.asarray(l2[3]), np.asarray(acc_lse[3]))


def test_fused_xla_matches_pallas_fwd():
    rng = np.random.default_rng(12)
    qs, kxt, vxt, tabs, acc_o, acc_lse, _ = _fused_setup(rng)
    o_x, l_x = ops.fused_run_attention(qs, kxt, vxt, acc_o, acc_lse, tabs,
                                       mask=True, impl="xla")
    o_p, l_p = ops.fused_run_attention(qs, kxt, vxt, acc_o, acc_lse, tabs,
                                       mask=True, impl="pallas",
                                       block_q=64, block_k=64,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x), atol=2e-6)
    live = np.asarray(l_x) > -1e29
    np.testing.assert_allclose(np.asarray(l_p)[live], np.asarray(l_x)[live],
                               atol=2e-6)


def test_fused_bwd_matches_xla_autodiff():
    """The merge-chain custom_vjp == plain autodiff of the batched XLA
    path, on live rows (dead-row accumulator cotangents are garbage that
    the executor discards at the zeros init)."""
    rng = np.random.default_rng(13)
    qs, kxt, vxt, tabs, acc_o, acc_lse, _ = _fused_setup(rng)
    key_o = jnp.asarray(rng.normal(size=qs.shape), jnp.float32)
    key_l = jnp.asarray(rng.normal(size=acc_lse.shape), jnp.float32)

    def loss(impl):
        def f(qs_, k_, v_, ao, al):
            o2, l2 = ops.fused_run_attention(
                qs_, k_, v_, ao, al, tabs, mask=True, impl=impl,
                block_q=64, block_k=64, interpret=True)
            return (jnp.sum(o2 * key_o)
                    + jnp.sum(jnp.where(l2 > -1e29, l2 * key_l, 0.0)))
        return f

    args = (qs, kxt, vxt, acc_o, acc_lse)
    g_x = jax.grad(loss("xla"), argnums=(0, 1, 2, 3, 4))(*args)
    g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b, name in zip(g_p[:3], g_x[:3], ["qs", "kxt", "vxt"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6,
                                   rtol=5e-6, err_msg=name)
    live = np.asarray(acc_lse) > -1e29           # incoming-acc live rows
    for a, b, name in zip(g_p[3:], g_x[3:], ["acc_o", "acc_lse"]):
        a, b = np.asarray(a), np.asarray(b)
        if a.ndim > live.ndim:
            m = np.broadcast_to(live[..., None], a.shape)
        else:
            m = live
        np.testing.assert_allclose(a[m], b[m], atol=5e-6, rtol=5e-6,
                                   err_msg=name)


def _run_chain(tabs, meta, n_runs):
    """``tabs`` cut into ``n_runs`` consecutive runs (each q-slot sorted
    and with its own kv-sorted backward order), as the executor does."""
    _, _, kv_seg, kv_pos = meta
    sq, skv = np.asarray(tabs["step_q"]), np.asarray(tabs["step_kv"])
    cuts = np.linspace(0, len(sq), n_runs + 1).astype(int)
    runs = []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        q_, kv_ = sq[lo:hi], skv[lo:hi]
        order = np.lexsort((q_, kv_))
        runs.append(dict(
            step_q=jnp.asarray(q_), step_kv=jnp.asarray(kv_),
            q_seg=tabs["q_seg"], q_pos=tabs["q_pos"],
            k_seg=kv_seg[kv_], k_pos=kv_pos[kv_],
            bwd_q=jnp.asarray(q_[order]), bwd_kv=jnp.asarray(kv_[order]),
            k_seg_b=kv_seg[kv_[order]], k_pos_b=kv_pos[kv_[order]]))
    return runs


def _remat_chain_loss(rng, policy, n_runs, save_out):
    """A checkpointed body folding a chain of fused runs, as one layer of
    the executor does, and its inputs."""
    qs, kxt, vxt, tabs, acc_o, acc_lse, meta = _fused_setup(rng)
    runs = _run_chain(tabs, meta, n_runs)
    key_o = jnp.asarray(rng.normal(size=qs.shape), jnp.float32)
    key_l = jnp.asarray(rng.normal(size=acc_lse.shape), jnp.float32)

    def body(qs_, k_, v_, o, lse):
        for t in runs:
            o, lse = ops.fused_run_attention(
                qs_, k_, v_, o, lse, t, mask=True, impl="pallas",
                block_q=64, block_k=64, interpret=True, save_out=save_out)
        return (jnp.sum(o * key_o)
                + jnp.sum(jnp.where(lse > -1e29, lse * key_l, 0.0)))

    return apply_remat(body, policy), (qs, kxt, vxt, acc_o, acc_lse)


@pytest.mark.parametrize("policy,n_runs,save_out,launches", [
    ("dots", 1, True, 1),        # the run's (o, lse) kept: no rerun
    ("dots", 1, False, 2),       # unnamed: the backward reruns it
    ("nothing", 1, True, 2),     # the name alone keeps nothing
    ("dots", 2, False, 4),       # multi-run plans: 2 per run, as before
])
def test_fused_fwd_launches_under_remat(policy, n_runs, save_out,
                                        launches):
    """Forward kernel launches in the grad of a checkpointed body: the
    "dots" policy keeps a run's named output, so the backward launches
    no second ``fused_flash_fwd``."""
    loss, args = _remat_chain_loss(np.random.default_rng(14), policy,
                                   n_runs, save_out)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    assert count_kernel_calls(jaxpr.jaxpr, "_fused_fwd_kernel") == launches


def test_fused_saved_output_grads_match_rerun():
    """Gradients from the saved run output == gradients from the rerun
    forward (the kernel is deterministic), on live rows."""
    grads = []
    for save_out in (True, False):
        loss, args = _remat_chain_loss(np.random.default_rng(15), "dots",
                                       1, save_out)
        grads.append(jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
            *args))
    live = np.asarray(args[4]) > -1e29           # incoming-acc live rows
    for a, b, name in zip(*grads, ["qs", "kxt", "vxt", "acc_o", "acc_lse"]):
        a, b = np.asarray(a), np.asarray(b)
        if name.startswith("acc"):
            m = np.broadcast_to(live.reshape(live.shape + (1,) * (
                a.ndim - live.ndim)), a.shape)
            a, b = a[m], b[m]
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6,
                                   err_msg=name)


# --------------------------------------------------------------------------
# mask-family kernel parity: window / chunk terms of _mask_tile
# (per-step pallas, fused pallas, fused xla, and xla fallback impls)
# --------------------------------------------------------------------------

# tile-boundary windows on purpose: W % block_k != 0 exercises windows
# that start/end mid-tile in every kv tile of the sweep
MASK_CASES = [
    masks.sliding_window(96),            # < one 128-tile, unaligned
    masks.sliding_window(160),           # spans two tiles, unaligned
    masks.sliding_window(128),           # exactly one tile
    masks.chunked(96),                   # chunk boundary mid-tile
    masks.chunked(192),
    masks.FULL,
]


@pytest.mark.parametrize("mask", MASK_CASES, ids=str)
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_masked_fwd_matches_oracle(mask, impl):
    """Per-step kernels under window/chunk masks vs the dense oracle."""
    h, kh, sq, sk, d, bq, bk = 4, 2, 256, 384, 64, 128, 128
    rng = np.random.default_rng(
        zlib.crc32(f"{mask}/{impl}".encode()))
    q, k, v, sq_, pq_, sk_, pk_ = _make_inputs(rng, h, kh, sq, sk, d,
                                               jnp.float32)
    o_ref, lse_ref = ref.reference_attention(q, k, v, sq_, pq_, sk_, pk_,
                                             mask)
    o, lse = ops.block_attention(q, k, v, sq_, pq_, sk_, pk_, mask=mask,
                                 impl=impl, block_q=bq, block_k=bk,
                                 interpret=True, xla_chunk=128)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    live = np.asarray(lse_ref) > -1e29
    np.testing.assert_allclose(np.asarray(lse)[live],
                               np.asarray(lse_ref)[live], atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("mask", MASK_CASES, ids=str)
def test_masked_bwd_matches_autodiff(mask):
    """Pallas backward kernels (dq, dk, dv) under window/chunk masks vs
    autodiff of the dense oracle (dlse included — the FCP merge case)."""
    h, kh, sq, sk, d, bq, bk = 4, 2, 256, 256, 32, 128, 128
    rng = np.random.default_rng(zlib.crc32(f"bwd/{mask}".encode()))
    q, k, v, sq_, pq_, sk_, pk_ = _make_inputs(rng, h, kh, sq, sk, d,
                                               jnp.float32)

    def loss_ref(q, k, v):
        o, lse = ref.reference_attention(q, k, v, sq_, pq_, sk_, pk_, mask)
        return jnp.sum(o * o) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0))

    def loss_pl(q, k, v):
        o, lse = ops.block_attention(q, k, v, sq_, pq_, sk_, pk_,
                                     mask=mask, impl="pallas",
                                     block_q=bq, block_k=bk, interpret=True)
        return jnp.sum(o * o) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_pl, g_ref, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("mask", MASK_CASES, ids=str)
def test_masked_fused_impls_match(mask):
    """Fused schedule-driven kernels (pallas custom_vjp vs batched-XLA
    autodiff) agree under window/chunk masks — outputs and gradients."""
    rng = np.random.default_rng(zlib.crc32(f"fused/{mask}".encode()))
    qs, kxt, vxt, tabs, acc_o, acc_lse, _ = _fused_setup(rng)
    o_x, l_x = ops.fused_run_attention(qs, kxt, vxt, acc_o, acc_lse, tabs,
                                       mask=mask, impl="xla")
    o_p, l_p = ops.fused_run_attention(qs, kxt, vxt, acc_o, acc_lse, tabs,
                                       mask=mask, impl="pallas",
                                       block_q=64, block_k=64,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x), atol=2e-6)
    live = np.asarray(l_x) > -1e29
    np.testing.assert_allclose(np.asarray(l_p)[live], np.asarray(l_x)[live],
                               atol=2e-6)

    key_o = jnp.asarray(rng.normal(size=qs.shape), jnp.float32)
    key_l = jnp.asarray(rng.normal(size=acc_lse.shape), jnp.float32)

    def loss(impl):
        def f(qs_, k_, v_):
            o2, l2 = ops.fused_run_attention(
                qs_, k_, v_, acc_o, acc_lse, tabs, mask=mask, impl=impl,
                block_q=64, block_k=64, interpret=True)
            return (jnp.sum(o2 * key_o)
                    + jnp.sum(jnp.where(l2 > -1e29, l2 * key_l, 0.0)))
        return f

    g_x = jax.grad(loss("xla"), argnums=(0, 1, 2))(qs, kxt, vxt)
    g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2))(qs, kxt, vxt)
    for a, b, name in zip(g_p, g_x, ["qs", "kxt", "vxt"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6,
                                   rtol=5e-6, err_msg=name)


def test_masked_fused_window_seeds_from_accumulator():
    """A windowed fused run merged with an incoming accumulator built
    from the same window is exactly the reference over the KV union —
    the cross-run seeding path with a non-causal-family mask."""
    mask = masks.sliding_window(160)                 # 160 % 64 != 0
    rng = np.random.default_rng(21)
    SL, H, KH, bs, d, EX = 4, 2, 2, 128, 32, 6
    qs = jnp.asarray(rng.normal(size=(SL, H, bs, d)), jnp.float32)
    kxt = jnp.asarray(rng.normal(size=(EX, KH, bs, d)), jnp.float32)
    vxt = jnp.asarray(rng.normal(size=(EX, KH, bs, d)), jnp.float32)
    q_seg = jnp.zeros((SL, bs), jnp.int32).at[SL - 1].set(-1)
    q_pos = (jnp.arange(bs, dtype=jnp.int32)[None]
             + jnp.arange(SL, dtype=jnp.int32)[:, None] * bs)
    kv_seg = jnp.zeros((EX, bs), jnp.int32).at[EX - 1].set(-1)
    kv_pos = (jnp.arange(bs, dtype=jnp.int32)[None]
              + jnp.arange(EX, dtype=jnp.int32)[:, None] * bs)
    # run: slot 1 consumes kv row 1 now; kv row 0 arrived "last run"
    step_q = jnp.asarray([1], jnp.int32)
    step_kv = jnp.asarray([1], jnp.int32)
    tabs = dict(step_q=step_q, step_kv=step_kv, q_seg=q_seg, q_pos=q_pos,
                k_seg=kv_seg[step_kv], k_pos=kv_pos[step_kv],
                bwd_q=step_q, bwd_kv=step_kv,
                k_seg_b=kv_seg[step_kv], k_pos_b=kv_pos[step_kv])
    acc_o = jnp.zeros((SL, H, bs, d), jnp.float32)
    acc_lse = jnp.full((SL, H, bs), ref.NEG_INF, jnp.float32)
    o_prev, l_prev = ref.reference_attention(
        qs[1], kxt[0], vxt[0], q_seg[1], q_pos[1], kv_seg[0], kv_pos[0],
        mask)
    acc_o = acc_o.at[1].set(o_prev)
    acc_lse = acc_lse.at[1].set(l_prev)
    o2, l2 = ops.fused_run_attention(qs, kxt, vxt, acc_o, acc_lse, tabs,
                                     mask=mask, impl="pallas",
                                     block_q=64, block_k=64, interpret=True)
    kk = jnp.concatenate([kxt[0], kxt[1]], axis=1)
    vv = jnp.concatenate([vxt[0], vxt[1]], axis=1)
    sk = jnp.concatenate([kv_seg[0], kv_seg[1]])
    pk = jnp.concatenate([kv_pos[0], kv_pos[1]])
    o_ref, l_ref = ref.reference_attention(qs[1], kk, vv, q_seg[1],
                                           q_pos[1], sk, pk, mask)
    np.testing.assert_allclose(np.asarray(o2[1]), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    live = np.asarray(l_ref) > -1e29
    np.testing.assert_allclose(np.asarray(l2[1])[live],
                               np.asarray(l_ref)[live], atol=2e-5,
                               rtol=2e-5)


def test_mask_tile_matches_mask_matrix():
    """The kernel-side _mask_tile == the oracle-side mask_matrix for all
    families (same predicate, two implementations)."""
    rng = np.random.default_rng(3)
    n = 192
    seg = jnp.asarray(rng.integers(-1, 3, size=n).astype(np.int32))
    pos = jnp.asarray(rng.integers(0, 500, size=n).astype(np.int32))
    for mask in [masks.CAUSAL, masks.FULL] + MASK_CASES:
        a = np.asarray(fa._mask_tile(seg, pos, seg, pos, mask))
        b = np.asarray(ref.mask_matrix(seg, pos, seg, pos, mask))
        np.testing.assert_array_equal(a, b, err_msg=str(mask))
