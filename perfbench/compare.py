"""The numbers that decide ``correct``: a run's warm-up epochs against the
plain reference (``reference.run_epochs``) from the same weights and data.

Adam's first update is about ``lr * sign(g)`` whatever the size of ``g``,
and the prediction network P narrows to one LReLU unit, so a rounding
that flips the sign of a small gradient component, or of that unit's
input, moves a client's later steps by a whole update: the worst leaf
differs by 10-100% on some seeds between any two precisions (section 6
of PERF.md).  Medians over clients and leaves are steady from seed to
seed; the worst client's median over its leaves catches a fault that hits
one client:

* ``val_gap``: over every client's validation MSE after each epoch, the
  median gap to the reference's, as a share of the reference's;
* ``test_gap``: the same over every client's test MSE of its best
  parameters (save-best);
* ``delta_gap``: over every client and parameter leaf, the median gap
  between the norm of the leaf's change over the epochs and the
  reference's, over the larger of the reference's norm of that leaf's
  change and of the client's median leaf's; leaves whose reference
  gradient is nought to rounding (Adam's first moment under a thousandth
  of the median leaf's) are left out;
* ``client_gap``: the worst client's median over its leaves of that gap,
  which one client left untrained or unwritten moves to about 1.

The Eq.-7 choices are replayed, and each choice's gap to the reference's
best score is printed (``worst``: the median, the widest, the share over a
few tolerances) but not judged: blending makes the pool's heads near
twins, so after two epochs a third to a half of a sound run's choices
differ from the reference's best by rounding, and the bf16 control's
median choice gap lies within 3x of sound runs' (section 6 of PERF.md).

``worst`` gives the worst client, leaf or choice of each, printed for a
reader and never judged.
"""
from __future__ import annotations

import jax
import numpy as np

SKIP_BELOW = 1e-3


def _leaves(tree):
    return [np.asarray(a, np.float64)
            for a in jax.tree_util.tree_leaves(tree)]


def _gaps(prog, ref, skip=None):
    """Per client and leaf: |norm(prog) - norm(ref)| over the larger of
    norm(ref) and the client's median leaf norm."""
    out = []
    for c, (p, r) in enumerate(zip(prog, ref)):
        pn = np.array([np.linalg.norm(a) for a in p])
        rn = np.array([np.linalg.norm(a) for a in r])
        gap = np.abs(pn - rn) / np.maximum(np.maximum(rn, np.median(rn)),
                                           1e-30)
        out.extend(gap[~skip[c]] if skip is not None else gap)
    return np.asarray(out)


def _client_medians(prog, ref, skip):
    """Per client: the median over its leaves of the change gap."""
    return np.asarray([np.median(_gaps([p], [r], [k]))
                       for p, r, k in zip(prog, ref, skip)])


def _all(prog: dict, ref: dict, params0, exchange: bool) -> dict:
    rel = lambda a, b: abs(a - b) / max(abs(b), 1e-30)
    C = len(params0)
    val = np.array([rel(a, b) for i in range(C)
                    for a, b in zip(prog["val"][i], ref["val"][i])])
    test = np.array([rel(prog["test"][i], ref["test"][i])
                     for i in range(C)])
    if len(prog["val"][0]) != len(ref["val"][0]) or not np.all(
            np.isfinite(np.concatenate([np.ravel(prog["val"]),
                                        prog["test"]]))):
        val, test = np.full_like(val, np.inf), np.full_like(test, np.inf)
    p0 = [_leaves(t) for t in params0]
    skip = []
    for r in ref["m"]:
        rn = np.array([np.linalg.norm(a) for a in _leaves(r)])
        skip.append(rn < SKIP_BELOW * np.median(rn))
    delta = lambda run: [[a - b for a, b in zip(_leaves(t), z)]
                         for t, z in zip(run["params"], p0)]
    dp, dr = delta(prog), delta(ref)
    out = {"val_gap": val, "test_gap": test,
           "delta_gap": _gaps(dp, dr, skip),
           "client_gap": _client_medians(dp, dr, skip),
           "grad_gap": _gaps([_leaves(t) for t in prog["m"]],
                             [_leaves(t) for t in ref["m"]])}
    if exchange:
        g = np.asarray(ref["gaps"], np.float64)
        out["select_gap"] = g if g.size else np.asarray([np.inf])
    return out


def numbers(prog: dict, ref: dict, params0, exchange: bool) -> dict:
    """The compared numbers.  ``prog`` and ``ref`` carry per-client
    "params", "m", "val" (per epoch), "test"; ``ref`` also "gaps"."""
    a = _all(prog, ref, params0, exchange)
    out = {k: float(np.median(a[k]))
           for k in ("val_gap", "test_gap", "delta_gap")}
    out["client_gap"] = float(np.max(a["client_gap"]))
    return out


def worst(prog: dict, ref: dict, params0, exchange: bool) -> dict:
    """For a reader, never judged: the worst client, leaf or choice of each
    gap (and of Adam's first moment), the median choice gap, and the share
    of choices whose gap is over a few tolerances."""
    a = _all(prog, ref, params0, exchange)
    out = {k: float(np.max(v)) for k, v in a.items()}
    if exchange:
        g = a["select_gap"]
        out["select_gap_median"] = float(np.median(g))
        out.update({f"choice_share_{t:g}": float(np.mean(g > t))
                    for t in (0.0, 1e-4, 1e-3, 1e-2)})
    return out


def judge(nums: dict, limits: dict) -> bool:
    """True when every number is finite and within its limit."""
    return all(np.isfinite(nums[k]) and nums[k] <= limits[k] for k in nums)


def report(nums: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}}, the result line's last key."""
    return {k: {"value": nums[k], "limit": limits[k]} for k in nums}
