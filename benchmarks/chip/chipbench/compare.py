"""The comparison that decides ``correct`` for a training cell.

Three numbers, each the worst case of its kind, each held against its
own limit from ``limits/<cell>.json``:

``loss_gap``
    the largest gap, in nats, between the program's loss and the
    reference's over the first two steps, which both see the initial
    weights (the warm-up's learning rate is 0 at the first step).  The
    third step's loss is left out: on seeds whose batches repeat a
    pattern one update takes it from ~12 to ~3, and its gap swells
    with that fall;
``grad_norm_gap``
    the worst leaf's gap between the two norms of the first gradient
    as AdamW got it, over the larger of that leaf's reference norm and
    the median leaf's;
``update_norm_gap``
    the same for the change of the weights over the three steps,
    leaving out leaves whose reference gradient is under a thousandth
    of the median leaf's (they move by round-off alone).

A norm gap compares two norms, not the norm of a difference: it asks
whether each leaf moved as far as the reference moved it.
"""

from __future__ import annotations

import math
import statistics

NAMES = ("loss_gap", "grad_norm_gap", "update_norm_gap")
LOSS_STEPS = 2        # steps whose losses are compared
QUIET = 1e-3          # of the median leaf's reference gradient


def worst_leaf(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    leaves = list(leaves)
    med = statistics.median(ref[k] for k in leaves)
    worst, at = 0.0, ""
    for k in leaves:
        den = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / den if den > 0 else math.inf
        if not math.isfinite(prog[k]):
            gap = math.inf
        if gap > worst or not at:
            worst, at = gap, k
    return worst, at


def moved_leaves(ref) -> list[str]:
    med = statistics.median(ref.grad_norms.values())
    return [k for k, g in ref.grad_norms.items() if g >= QUIET * med]


def numbers(prog, ref) -> dict:
    """``{name: (value, where)}`` for the three numbers."""
    if set(prog.grad_norms) != set(ref.grad_norms):
        raise ValueError(f"leaves differ: {sorted(prog.grad_norms)} vs "
                         f"{sorted(ref.grad_norms)}")
    losses = [abs(a - b) if math.isfinite(a) else math.inf
              for a, b in zip(prog.losses[:LOSS_STEPS],
                              ref.losses[:LOSS_STEPS])]
    if len(losses) < LOSS_STEPS:
        losses.append(math.inf)
    i = max(range(len(losses)), key=losses.__getitem__)
    return {
        "loss_gap": (losses[i], f"step {i}"),
        "grad_norm_gap": worst_leaf(prog.grad_norms, ref.grad_norms,
                                    ref.grad_norms),
        "update_norm_gap": worst_leaf(prog.update_norms, ref.update_norms,
                                      moved_leaves(ref)),
    }


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per number, its value beside its limit."""
    out = {}
    for name in NAMES:
        value, where = nums[name]
        out[name] = {"value": value, "limit": float(limits[name]),
                     "at": where}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out
