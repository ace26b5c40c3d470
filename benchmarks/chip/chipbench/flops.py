"""The table of peaks that operations and bytes are held against, and the
counting that every architecture's FLOP count shares.  What a train
step of an architecture requires is counted in its module
(``archs/<name>.py``: ``model_flops``, ``attention_least_seconds``)."""

from __future__ import annotations

import json
import pathlib
from typing import Sequence

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
