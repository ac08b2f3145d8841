"""The comparison that decides ``correct``.

Three numbers, each a gap between what the program produced in its first
three steps and what the reference produced from the same seed and
batches, and one count of faults in the batches both were fed:

- ``loss_gap``: the largest relative gap of a step's reported loss;
- ``grad_gap``: over the workers and the leaves, the largest gap between
  the program's and the reference's norm of the first applied gradient,
  over the reference's norm of that leaf or of the median leaf,
  whichever is larger;
- ``change_gap``: the same for the norm of each leaf's change over the
  three steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (a leaf with no gradient moves by
  round-off alone);
- ``feed_faults``: rows repeated across the fed batches, plus positions
  whose label is not the next token.

A number is within its limit when it is finite and at most the limit; a
limit of ``None`` reports the number without judging it.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "feed_faults")
NO_GRADIENT = 1e-3   # of the median leaf's reference gradient norm


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Every leaf's gap between the two norms, over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref}


def worst_leaf_gap(prog, ref: dict, leaves=None) -> float:
    """prog: one reading {leaf: norm}, or a list of them, one a worker."""
    worst = 0.0
    for p in prog if isinstance(prog, list) else [prog]:
        g = leaf_gaps(p, ref)
        worst = max(worst, max(g[k] for k in (g if leaves is None
                                              else leaves)))
    return worst


def moving_leaves(ref_grad: dict) -> list:
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= NO_GRADIENT * med]


def gaps(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [...], "grad_norms": {leaf: norm},
    "change_norms": {leaf: norm}}; the program's norms may be a list with
    one reading a worker."""
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss = math.inf
    return {"loss_gap": loss,
            "grad_gap": worst_leaf_gap(prog["grad_norms"],
                                       ref["grad_norms"]),
            "change_gap": worst_leaf_gap(
                prog["change_norms"], ref["change_norms"],
                moving_leaves(ref["grad_norms"]))}


def feed_faults(batches: list) -> int:
    """batches: per step {"tokens", "labels"} of the global batch (B, S).
    Counts every row that repeats an earlier one (within or across the
    steps) and every position whose label is not the next token."""
    rows = np.concatenate([np.asarray(b["tokens"]) for b in batches])
    repeats = len(rows) - len(np.unique(rows, axis=0))
    shifted = sum(int(np.sum(np.asarray(b["labels"])[:, :-1]
                             != np.asarray(b["tokens"])[:, 1:]))
                  for b in batches)
    return int(repeats + shifted)


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every compared number."""
    out, ok = {}, True
    for name in NUMBERS:
        v, lim = values.get(name, math.nan), limits.get(name)
        out[name] = {"value": v, "limit": lim}
        if lim is not None and not (math.isfinite(v) and v <= lim):
            ok = False
    return ok, out
