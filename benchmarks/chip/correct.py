"""The comparison that decides ``correct``.

Three numbers, each held to the limit its cell file states:

- ``loss_gap``: the largest relative gap between the program's loss and
  the reference's over the first three steps.
- ``grad_gap``: for the first gradient as the optimizer received it
  (after clipping), the worst leaf's gap between the program's norm and
  the reference's, over the reference's norm of that leaf or of the
  median leaf, whichever is larger (some gradients are all but zero).
- ``change_gap``: the same for the norm of each leaf's change over the
  three steps. Leaves whose reference gradient stays under a thousandth
  of the median leaf's at every step move under AdamW by round-off alone
  and are left out.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Dict, List

NEGLIGIBLE = 1e-3      # of the median leaf's gradient norm


@dataclass
class Readings:
    """What one side produced over the first three steps."""
    losses: List[float]
    grad_norms: Dict[str, float]      # first step, after clipping
    change_norms: Dict[str, float]    # |master after three - start|


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    floor = statistics.median(want.values())
    return max(abs(got[k] - w) / max(w, floor) if max(w, floor) > 0 else 0.0
               for k, w in want.items())


def numbers(prog: Readings, ref) -> Dict[str, float]:
    """The three gaps between ``prog`` and a reference result."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog.losses, ref.losses))
    grad_gap = worst_leaf_gap(prog.grad_norms, ref.grad_norms[0])
    moved = {k: v for k, v in ref.change_norms.items()
             if any(g[k] >= NEGLIGIBLE * statistics.median(g.values())
                    for g in ref.grad_norms)}
    change_gap = worst_leaf_gap(prog.change_norms, moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def compare(prog: Readings, ref, limits: Dict[str, float]) -> dict:
    """{name: {"value", "limit"}} for every number the cell holds to a
    limit."""
    got = numbers(prog, ref)
    return {k: {"value": got[k], "limit": lim} for k, lim in limits.items()}
