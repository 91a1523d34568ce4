"""The comparison that decides `correct`.

Three numbers are compared, each with a limit of its own, set from
readings of sound runs and of the lower-precision control (PERF.md):

- `loss_gap`: the largest |program - reference| of the followed steps'
  losses (nats);
- `grad_gap`: over the leaves, the largest gap between the program's
  and the reference's norm of the first step's gradient as Adam got it,
  over the reference's norm of that leaf or of the median leaf,
  whichever is larger;
- `update_gap`: the same for the change of Adam's master weights over
  the followed steps;
- `grad_err`: over the leaves, the largest norm of the difference
  between the program's and the reference's first gradient, over the
  same denominator. The gaps of norms above average rounding away;
  this one sees the precision the gradient was computed in.

Leaves whose reference gradient is under a thousandth of the median
leaf's are nought to rounding and left out of both leaf numbers. Every
DP replica that holds a state of its own is compared; the worst counts.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from chipbench import reference

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "grad_err")
NEGLIGIBLE = 1e-3           # of the median leaf's reference gradient


def _norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(x, jnp.float32)))))


def program_readings(losses: List[float], grads: dict, masters: dict,
                     follow: dict, cfg: dict) -> dict:
    """The program's readings from its losses and its host copies of the
    first gradient and of the master weights, {(d, s): {leaf: array}};
    each leaf's list runs over the replicas that hold their own state."""
    out = {"losses": losses, "grad_norms": {}, "grads": {},
           "update_norms": {}}
    for s, name, sl in reference.stage_slices(cfg):
        w0 = follow["master0"][name][sl]
        for (d, ds), leaves in sorted(grads.items()):
            if ds == s:
                out["grads"].setdefault((s, name), []).append(leaves[name])
                out["grad_norms"].setdefault((s, name), []).append(
                    _norm(leaves[name]))
        for (d, ds), leaves in sorted(masters.items()):
            if ds == s:
                out["update_norms"].setdefault((s, name), []).append(
                    _norm(jnp.asarray(leaves[name]) - w0))
    return out


def follow_readings(follow: dict, cfg: dict, master0=None) -> dict:
    """The readings of a follow of the reference (or of a stand-in, with
    the reference's initial master weights)."""
    w0 = follow["master0"] if master0 is None else master0
    out = {"losses": follow["losses"], "grad_norms": {}, "grads": {},
           "update_norms": {}}
    for s, name, sl in reference.stage_slices(cfg):
        out["grads"][(s, name)] = follow["grads0"][name][sl]
        out["grad_norms"][(s, name)] = follow["grad_norms"][(s, name)]
        out["update_norms"][(s, name)] = _norm(follow["master"][name][sl]
                                               - w0[name][sl])
    return out


def _worst_leaf(got: Dict[Tuple[int, str], List[float]],
                want: Dict[Tuple[int, str], float], keep,
                floor: float = None, scale=None) -> float:
    """max over kept leaves and replicas of |got - want| / max(scale of
    the leaf, floor); the scale is `want` and the floor its median
    unless given."""
    scale = want if scale is None else scale
    if floor is None:
        floor = float(np.median([want[k] for k in keep]))
    return max(abs(g - want[k]) / max(scale[k], floor)
               for k in keep for g in got[k])


def gaps(got: dict, want: dict) -> Dict[str, float]:
    """The numbers of a run (`got`, whose per-leaf entries are lists over
    replicas) against the reference (`want`, one entry per leaf)."""
    ref_g = want["grad_norms"]
    median = float(np.median(list(ref_g.values())))
    keep = [k for k, n in ref_g.items() if n >= NEGLIGIBLE * median]
    err = {k: [_norm(jnp.asarray(g) - want["grads"][k]) for g in gs]
           for k, gs in got["grads"].items()}
    n = len(want["losses"])
    return {
        "loss_gap": max(abs(a - b) for a, b in
                        zip(got["losses"][:n], want["losses"])),
        "grad_gap": _worst_leaf(got["grad_norms"], ref_g, keep),
        "update_gap": _worst_leaf(got["update_norms"],
                                  want["update_norms"], keep),
        "grad_err": _worst_leaf(err, {k: 0.0 for k in ref_g}, keep,
                                floor=median, scale=ref_g),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is a number within its limit; a number
    without a limit fails, so an uncalibrated configuration never passes."""
    return all(limits.get(k) is not None and np.isfinite(numbers[k])
               and numbers[k] <= limits[k] for k in NUMBERS)


def lines(numbers: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"{k} {numbers[k]!r} limit {limits.get(k)!r}" for k in NUMBERS]
