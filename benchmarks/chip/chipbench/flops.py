"""Operations and bytes that a train step requires, counted from shapes
and document lengths, and the table of peaks they are held against.

Recomputed work (rematerialisation, the kernel's own recompute) is not
counted: these are what the algorithm needs, so a share of a peak says
how close the implementation comes to it."""

from __future__ import annotations

import json
import pathlib
from typing import Sequence

from .model_cfg import Dims

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: "
                       f"{sorted(table['devices'])}") from None


def causal_pairs(seqlens: Sequence[int]) -> int:
    """(query, key) pairs a causal mask keeps within each document."""
    return sum(int(n) * (int(n) + 1) // 2 for n in seqlens)


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
