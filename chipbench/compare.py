"""The comparison that decides ``correct``.

Four numbers, each against a limit of its own from the cell's file:

- ``loss_gap``: the relative gap between the program's loss and the
  reference's at the first step, where both start from the same weights
  and rows and differ only by arithmetic;
- ``later_loss_gap``: the largest relative gap of the losses of the second
  and third steps, which also carry the two sides' different draws;
- ``grad_gap``: over the counted leaves, the largest gap between the norm
  of the program's first gradient, worked out from its state after one
  step (``m / (1 - beta1)``, the synced share, plus the error-feedback
  residual: the gradient the compressor was handed) and the reference's
  worked out alike, over the larger of the reference's norm of that leaf
  and of the median leaf. The synced share alone carries the two sides'
  independent selections, whose noise in the small leaves no limit
  separates from the control's (PERF.md); their sum does not;
- ``change_gap``: the same for the norm of each leaf's change over the
  three steps.

A leaf counts unless the reference's dense gradient of it is under a
thousandth of the median leaf's: such a leaf (a key bias under softmax)
moves under Adam by round-off alone. The two sides draw their own
selections, so the later losses and the changes carry the spread of the
estimator; the limits are set from readings of sound runs over many seeds,
of the control and of the faults (see PERF.md)."""
from __future__ import annotations

import math

NAMES = ("loss_gap", "later_loss_gap", "grad_gap", "change_gap")
QUIET = 1e-3     # a leaf under this share of the median's gradient: left out


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _worst(prog, ref, counted, paths):
    med = _median([ref[i] for i in counted])
    gap, leaf = 0.0, None
    for i in counted:
        den = max(ref[i], med)
        g = abs(prog[i] - ref[i]) / den if den > 0 else math.inf
        if not g <= gap:          # also takes a NaN
            gap, leaf = g, paths[i]
    return gap, leaf


def numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``loss`` (per step), ``first_grad_norm``
    and ``change_norm`` (per leaf, in ``ref["paths"]`` order); ``ref`` also
    ``dense_grad_norm``. Returns the four numbers and which step or leaf
    set each."""
    paths = ref["paths"]
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    dense = ref["dense_grad_norm"]
    floor = QUIET * _median(dense)
    counted = [i for i, g in enumerate(dense) if g >= floor]
    grad, grad_leaf = _worst(prog["first_grad_norm"], ref["first_grad_norm"],
                             counted, paths)
    change, change_leaf = _worst(prog["change_norm"], ref["change_norm"],
                                 counted, paths)
    return {"loss_gap": loss[0], "later_loss_gap": max(loss[1:]),
            "grad_gap": grad, "change_gap": change, "grad_leaf": grad_leaf,
            "change_leaf": change_leaf,
            "left_out": [paths[i] for i in range(len(paths))
                         if i not in counted]}


def verdict(nums: dict, limits: dict) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(math.isfinite(nums[k]) and nums[k] <= limits[k]
               for k in NAMES)


def lines(nums: dict, limits: dict) -> list[str]:
    """Each number beside its limit, for the last lines of standard error."""
    return [f"check {k} {nums[k]!r} limit {limits[k]!r}" for k in NAMES]


def summary(nums: dict, limits: dict) -> dict:
    """The result line's ``checks``: each number with its limit."""
    return {k: {"value": nums[k], "limit": limits[k]} for k in NAMES}
