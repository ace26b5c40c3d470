"""FLOP and byte counts against hand counts, and against the counts as
they read before they moved into the dense architecture's module."""

import json
import math

import pytest

from tests import tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import flops, registry
from chipbench.archs import dense

STABLELM = dense.Dims(n_layers=1, d_model=2048, n_heads=32, n_kv_heads=32,
                head_dim=64, d_ff=5632, vocab=100352, qkv_bias=True,
                rope_theta=1e4, norm_eps=1e-5, param_dtype="bfloat16")
CODEQWEN = dense.Dims(n_layers=1, d_model=4096, n_heads=32, n_kv_heads=4,
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
    assert dense.attention_flops(STABLELM, [L]) == attn
    assert dense.model_flops(STABLELM, [L]) == (
        6 * STABLELM.matmul_params() * L + attn)
    # bytes: fwd Q,K,V read + O write; bwd Q,K,V,O,dO read + dQ,dK,dV
    t = 32 * 64 * L
    assert dense.attention_bytes(STABLELM, [L]) == (4 * t + 8 * t) * 2


def test_packed_composition():
    lens = [7332, 860]
    pairs = 7332 * 7333 // 2 + 860 * 861 // 2
    assert flops.causal_pairs(lens) == pairs
    assert dense.attention_flops(CODEQWEN, lens) == 12 * 32 * 128 * pairs
    q = 32 * 128 * 8192
    kv = 4 * 128 * 8192
    assert dense.attention_bytes(CODEQWEN, lens) == (
        (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)) * 2
    # packing splits the work: two documents need less than one of 8192
    assert (dense.attention_flops(CODEQWEN, lens)
            < dense.attention_flops(CODEQWEN, [8192]))


def test_least_time_takes_the_binding_roof():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = dense.attention_least_seconds(STABLELM, [8192], peak)
    assert bound == "compute"
    assert math.isclose(t, dense.attention_flops(STABLELM, [8192]) / 197e12)
    # many tiny documents: little work per byte
    t, bound = dense.attention_least_seconds(STABLELM, [16] * 512, peak)
    assert bound == "memory"


def test_peaks_table():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# (model_flops, least attention seconds) of each configuration file as
# the benchmark read it before the counts moved into archs/dense.py
PINNED = {
    "stablelm_1_6b": {
        (7332, 860): (13296973774848, 0.003399847333035533),
        (8192,): (13451938234368, 0.004186468954964467),
        (8012, 180): (13416495710208, 0.004006557664812183),
        (4096,): (6519810686976, 0.001046744984040609),
        (16,) * 512: (12628915126272, 0.0004916400293040293),
    },
    "codeqwen1_5_7b": {
        (7332, 860): (29918252236800, 0.006799694666071066),
        (8192,): (30228181155840, 0.008372937909928935),
        (8012, 180): (30157296107520, 0.008013115329624365),
        (4096,): (14701773717504, 0.002093489968081218),
        (16,) * 512: (28582134939648, 0.0005530950329670329),
    },
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_counts_as_pinned_before_the_move(config):
    bench = registry.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[config]
    cfg = json.loads((registry.ROOT / entry["file"]).read_text())
    arch = registry.arch(cfg.get("arch", "dense"))
    dims = arch.sizes(cfg)
    for lens, (work, least) in PINNED[config].items():
        assert arch.model_flops(dims, lens) == work
        assert arch.attention_least_seconds(dims, lens, V5E)[0] == least
