"""The comparison that decides ``correct``.

A training cell drives the program's compiled round loop through its
first ``steps`` dispatches, in one call made as the window's call is
made, and the plain reference through the same rounds, from the same
weights and data.  Four numbers are read; those that ``cells/<cell>.json``
gives a limit are compared with it, and a cell leaves out a number that
no limit can hold there (one that sound runs read as high as the
control does):

  loss     the largest gap, over every round of that call, between the
           round loss the program reports and the reference's, over the
           larger of the reference's loss of that round and its median
           round loss (a loss that nears 0 late in training would
           otherwise magnify rounding)
  loss1    the same gap in the first round alone, over the reference's
           loss of that round: steady from seed to seed where the later
           rounds' gaps swing
  update1  the first update as the server applies it, ``p1 - p0``: by
           the worst leaf, the gap between the program's norm and the
           reference's, over the larger of the reference's norm of that
           leaf and of the median leaf
  change3  the same of the whole change after the steps, ``p3 - p0``

Leaves whose first update in the reference is under a thousandth of the
median leaf's move by round-off alone (a key bias under softmax), and
are left out of both norms by that rule, never by name.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

NUMBERS = ("loss", "loss1", "update1", "change3")
QUIET = 1e-3        # of the median leaf: a leaf that moves by round-off


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray
             ) -> Tuple[float, int]:
    """(worst gap, index of its leaf) over the kept leaves."""
    med = float(np.median(ref[keep]))
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    gap = np.where(keep, gap, -np.inf)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def readings(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             names: Sequence[str]) -> Dict[str, object]:
    """The numbers of one run.  ``prog`` and ``ref`` each hold
    ``losses`` (one per round), ``update1`` and ``change3`` (one norm per
    leaf, in the order of ``names``)."""
    lp, lr = (np.asarray(x["losses"], np.float64) for x in (prog, ref))
    scale = np.maximum(np.abs(lr), np.median(np.abs(lr)))
    loss = float(np.max(np.abs(lp - lr) / scale))
    loss1 = float(abs(lp[0] - lr[0]) / abs(lr[0]))
    r1 = np.asarray(ref["update1"], np.float64)
    keep = r1 >= QUIET * np.median(r1)
    out: Dict[str, object] = {"loss": loss, "loss1": loss1}
    for key in ("update1", "change3"):
        g, i = norm_gap(np.asarray(prog[key], np.float64),
                        np.asarray(ref[key], np.float64), keep)
        out[key] = g
        out[f"{key}_leaf"] = names[i]
    out["left_out"] = [n for n, k in zip(names, keep) if not k]
    return out


def judge(read: Dict[str, object], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Whether every compared number is finite and within its limit, and
    those numbers beside their limits."""
    checks = {k: {"value": float(read[k]), "limit": float(limits[k])}
              for k in NUMBERS if k in limits}
    if not checks:
        raise ValueError(f"no limit for any of {NUMBERS}")
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def lines(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"{k} {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]
