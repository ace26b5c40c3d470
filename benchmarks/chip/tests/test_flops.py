"""FLOP and byte counts against hand counts."""

import math

import pytest

from tests import tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import flops
from chipbench.model_cfg import Dims

STABLELM = Dims(n_layers=1, d_model=2048, n_heads=32, n_kv_heads=32,
                head_dim=64, d_ff=5632, vocab=100352, qkv_bias=True,
                rope_theta=1e4, norm_eps=1e-5, param_dtype="bfloat16")
CODEQWEN = Dims(n_layers=1, d_model=4096, n_heads=32, n_kv_heads=4,
                head_dim=128, d_ff=13440, vocab=92416, qkv_bias=True,
                rope_theta=1e6, norm_eps=1e-5, param_dtype="bfloat16")


def test_matmul_params_by_hand():
    # q, k, v, o: 4 x 2048 x 2048; ffn: 3 x 2048 x 5632; head 2048 x 100352
    assert STABLELM.matmul_params() == (4 * 2048 * 2048 + 3 * 2048 * 5632
                                        + 2048 * 100352)
    # GQA: q and o 4096 x 4096, k and v 4096 x 512
    assert CODEQWEN.matmul_params() == (2 * 4096 * 4096 + 2 * 4096 * 512
                                        + 3 * 4096 * 13440 + 4096 * 92416)


def test_one_document():
    L = 8192
    pairs = L * (L + 1) // 2
    assert flops.causal_pairs([L]) == pairs
    attn = 12 * 32 * 64 * pairs
    assert flops.attention_flops(STABLELM, [L]) == attn
    assert flops.model_flops(STABLELM, [L]) == (
        6 * STABLELM.matmul_params() * L + attn)
    # bytes: fwd Q,K,V read + O write; bwd Q,K,V,O,dO read + dQ,dK,dV
    t = 32 * 64 * L
    assert flops.attention_bytes(STABLELM, [L]) == (4 * t + 8 * t) * 2


def test_packed_composition():
    lens = [7332, 860]
    pairs = 7332 * 7333 // 2 + 860 * 861 // 2
    assert flops.causal_pairs(lens) == pairs
    assert flops.attention_flops(CODEQWEN, lens) == 12 * 32 * 128 * pairs
    q = 32 * 128 * 8192
    kv = 4 * 128 * 8192
    assert flops.attention_bytes(CODEQWEN, lens) == (
        (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)) * 2
    # packing splits the work: two documents need less than one of 8192
    assert (flops.attention_flops(CODEQWEN, lens)
            < flops.attention_flops(CODEQWEN, [8192]))


def test_least_time_takes_the_binding_roof():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.attention_least_seconds(STABLELM, [8192], peak)
    assert bound == "compute"
    assert math.isclose(t, flops.attention_flops(STABLELM, [8192]) / 197e12)
    # many tiny documents: little work per byte
    t, bound = flops.attention_least_seconds(STABLELM, [16] * 512, peak)
    assert bound == "memory"


def test_peaks_table():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
