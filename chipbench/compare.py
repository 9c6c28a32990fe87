"""The comparison that decides `correct` for a training cell.

The trainer and the reference each follow the plan's first slots from the
same seed, up to the last of the traffic's ``check_slots``.  These numbers
are read from the two:

  loss           worst relative gap of a worker's loss, over workers and
                 the check slots;
  loss1          the same over the first slot alone;
  grad1          the first gradient as the optimizer applied it, read from
                 the state: per worker and leaf, the norm of (initial -
                 after slot 1); the worst leaf's gap;
  grad1_median   the median leaf's gap of the same norms;
  change         per worker and leaf, the norm of (initial - after the
                 last check slot); the worst leaf's gap;
  change_median  the median leaf's gap of the same norms.
  exchange       the spread over workers of the same norms, per leaf:
                 max - min over the workers of a leaf's norm; the worst
                 leaf's gap between the two spreads.  After a hub event
                 every worker holds the same model, so the reference's
                 spread is 0, and a program that leaves out the exchange
                 reads the workers' own differences.

The gap of a leaf is |norm_program - norm_reference| over the larger of
the reference's norm of that leaf and the median leaf's norm.  Leaves
whose first gradient in the reference is under a thousandth of the median
leaf's (a key bias under softmax, whose gradient is zero but for
rounding) are left out: a bf16 optimizer moves them by round-off alone.

A cell's limits file (`limits/<cell>.json`) names the numbers that are
compared, each with its limit; the others are printed for the record.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3


def negligible(grad_norms: dict) -> set:
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v < NEGLIGIBLE * med}


def leaf_gaps(prog: dict, ref: dict, skip: set) -> dict:
    """{leaf: gap} over the leaves not in ``skip``."""
    keys = [k for k in ref if k not in skip]
    moved = [ref[k] for k in keys if ref[k] > 0]
    med = float(np.median(moved)) if moved else 0.0
    out = {}
    for k in keys:
        den = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / den if den > 0 else \
            (0.0 if prog[k] == 0 else math.inf)
        out[k] = gap if gap == gap else math.inf      # nan reads as inf
    return out


def spreads(norms: dict) -> dict:
    """{leaf: max - min over workers} of per-worker norms keyed
    "w<i>/<leaf>"."""
    by_leaf = {}
    for k, v in norms.items():
        by_leaf.setdefault(k.split("/", 1)[1], []).append(v)
    return {k: max(v) - min(v) for k, v in by_leaf.items()}


def exchange_gaps(prog: dict, ref: dict, skip: set) -> dict:
    """{leaf: |spread_program - spread_reference| / scale}, the scale as
    in `leaf_gaps`: the larger of the leaf's own norm in the reference
    (its largest over workers) and the median leaf's."""
    keep = [k for k in ref if k not in skip]
    scale = {}
    for k in keep:
        leaf = k.split("/", 1)[1]
        scale[leaf] = max(scale.get(leaf, 0.0), ref[k])
    moved = [v for v in scale.values() if v > 0]
    med = float(np.median(moved)) if moved else 0.0
    sp = spreads({k: prog[k] for k in keep})
    sr = spreads({k: ref[k] for k in keep})
    out = {}
    for leaf, den in scale.items():
        den = max(den, med)
        gap = abs(sp[leaf] - sr[leaf]) / den if den > 0 else \
            (0.0 if sp[leaf] == sr[leaf] else math.inf)
        out[leaf] = gap if gap == gap else math.inf
    return out


def worst(gaps: dict) -> tuple[float, str]:
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def median(gaps: dict) -> tuple[float, str]:
    return float(np.median(list(gaps.values()))), "median leaf"


def loss_gaps(prog: dict, ref: dict) -> np.ndarray:
    """(slots, workers) relative loss gaps; nan reads as inf."""
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    rel = np.abs(lp - lr) / np.abs(lr)
    return np.where(np.isnan(rel), np.inf, rel)


def loss_by_slot(prog: dict, ref: dict) -> list:
    """Each check slot's worst relative loss gap over workers."""
    return [float(x) for x in loss_gaps(prog, ref).max(axis=1)]


def change_median_by_slot(prog: dict, ref: dict) -> list:
    """The median leaf's change gap after each check slot."""
    skip = negligible(ref["grad1"])
    return [median(leaf_gaps(p, r, skip))[0]
            for p, r in zip(prog["deltas"], ref["deltas"])]


def numbers(prog: dict, ref: dict) -> dict:
    """{name: (value, where)} for every number named above."""
    skip = negligible(ref["grad1"])
    rel = loss_gaps(prog, ref)
    i = np.unravel_index(np.argmax(rel), rel.shape)
    out = {"loss": (float(rel[i]), f"slot {ref['slots'][i[0]]} worker {i[1]}"),
           "loss1": (float(rel[0].max()),
                     f"slot 1 worker {int(np.argmax(rel[0]))}")}
    for name, key in (("grad1", "delta1"), ("change", "delta_last")):
        gaps = leaf_gaps(prog[key], ref[key], skip)
        out[name] = worst(gaps)
        out[name + "_median"] = median(gaps)
    out["exchange"] = worst(exchange_gaps(prog["delta_last"],
                                          ref["delta_last"], skip))
    return out


def check(prog: dict, ref: dict, limits: dict) -> tuple[bool, dict, dict]:
    """(correct, {compared name: {"value", "limit", "where"}},
    {every number: value})."""
    nums = numbers(prog, ref)
    rows = {k: {"value": nums[k][0], "limit": lim, "where": nums[k][1]}
            for k, lim in limits.items()}
    ok = all(r["value"] <= r["limit"] for r in rows.values())
    return ok, rows, {k: v for k, (v, _) in nums.items()}
