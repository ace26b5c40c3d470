"""The one generator of training traffic: a traffic file's parameters in,
the bounded set of packed-batch compositions out.

A composition is the list of document lengths that fill one step's
token budget exactly.  The set is drawn from a fixed seed, so every run
of a cell trains the same work and compiles the same programs; the
run's seed only orders the set (and draws the tokens and the
weights)."""

from __future__ import annotations

import numpy as np

COMPOSITION_SEED = 0


def _lengths(spec: dict, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    kind = spec["kind"]
    if kind == "lognormal":
        sigma = float(spec["sigma"])
        # E[lognormal(mu, sigma)] = exp(mu + sigma^2 / 2)
        mu = np.log(float(spec["mean"])) - sigma ** 2 / 2
        x = rng.lognormal(mu, sigma, size=n)
    elif kind == "uniform":
        x = np.full(n, int(spec["length"]), dtype=np.float64)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(x.astype(np.int64), int(spec.get("min_len", 1)),
                   int(spec.get("max_len", 1 << 30)))


def composition(spec: dict, budget: int, seed: int) -> list[int]:
    """Document lengths filling ``budget`` tokens: draw lengths, clip each
    to what is left, stop when the budget is full or the remainder is
    shorter than half the minimum length, and top up the last
    document."""
    min_len = int(spec.get("min_len", 1))
    draws = _lengths(spec, 4 * max(1, budget // 4096), seed)
    out: list[int] = []
    for length in draws:
        length = min(int(length), budget - sum(out))
        if length < min_len // 2 or length <= 0:
            break
        out.append(length)
        if sum(out) >= budget:
            break
    if not out:
        out = [budget]
    out[-1] += budget - sum(out)
    return out


def compositions(traffic: dict) -> list[list[int]]:
    """The traffic's bounded set, in canonical order."""
    budget = int(traffic["workers"]) * int(traffic["tokens_per_worker"])
    return [composition(traffic["lengths"], budget, COMPOSITION_SEED + b)
            for b in range(int(traffic["compositions"]))]


def ordered(traffic: dict, seed: int) -> list[list[int]]:
    """The set in the order the run's seed gives it."""
    comps = compositions(traffic)
    order = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                   int(seed) >> 32, 0x7AFF]).permutation(
                                       len(comps))
    return [comps[i] for i in order]
