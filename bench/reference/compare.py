"""The numbers that decide ``correct`` for a training cell.

A run's first rounds, driven through the window's own call in set-up, are
set beside the plain reference's rounds from the same weights and data:

- ``loss_gap``: over those rounds, the largest |loss - reference loss| /
  |reference loss| of the round's client loss;
- ``update1_gap``: the first round's update (new global minus the
  initial), leaf by leaf: |norm(program) - norm(reference)| over the
  reference's norm of that leaf or of the median leaf, whichever is
  larger; the worst leaf;
- ``change_gap``: the same, of the global's change over all the rounds
  compared;
- ``resid_gap`` (error-feedback uplinks): over the rounds, the largest
  relative gap of the clients' mean residual norm.

Leaves the reference does not move are left out of both by one rule: a
leaf whose reference first update has a norm under a thousandth of the
median leaf's (a frozen leaf, or one whose gradient is nought to
rounding).
"""
from __future__ import annotations

import jax
import numpy as np

MOVED = 1e-3


def leaf_norms(tree_a, tree_b) -> list[float]:
    return [float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
            for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b))]


def leaf_gaps(prog: list[float], ref: list[float], keep: list[bool]) -> list[float]:
    """Each kept leaf's |norm - reference norm| over the larger of its and
    the median kept leaf's reference norm; a left-out leaf reads 0."""
    med = float(np.median([r for r, k in zip(ref, keep) if k]))
    return [abs(p - r) / max(r, med) if k else 0.0 for p, r, k in zip(prog, ref, keep)]


def norm_gap(prog: list[float], ref: list[float], keep: list[bool]) -> float:
    return max(leaf_gaps(prog, ref, keep))


def training_numbers(p0, prog: dict, ref: dict) -> dict[str, float]:
    """``prog`` and ``ref`` hold ``losses`` (one per round compared),
    ``first`` (the global after round 1, or None where the program's call
    runs several rounds and no global after round 1 exists) and ``last``
    (after the last round), and, for an error-feedback uplink, ``resid``:
    the mean residual norm after each round, compared as ``resid_gap``,
    the largest relative gap over the rounds."""
    ref1 = leaf_norms(ref["first"], p0)
    med = float(np.median(ref1))
    keep = [r >= MOVED * med for r in ref1]
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(prog["losses"], ref["losses"]))}
    if prog.get("first") is not None:
        out["update1_gap"] = norm_gap(leaf_norms(prog["first"], p0), ref1, keep)
    out["change_gap"] = norm_gap(leaf_norms(prog["last"], p0),
                                 leaf_norms(ref["last"], p0), keep)
    if prog.get("resid"):
        out["resid_gap"] = max(abs(a - b) / b for a, b in zip(prog["resid"], ref["resid"]))
    return out


def worst_leaves(p0, prog: dict, ref: dict, top: int = 1) -> dict[str, list]:
    """The ``top`` leaves with the largest ``update1_gap`` and
    ``change_gap``, worst first, each as (path in the parameter tree,
    size, gap)."""
    flat = jax.tree_util.tree_flatten_with_path(p0)[0]
    names = [(jax.tree_util.keystr(k), int(np.size(x))) for k, x in flat]
    ref1 = leaf_norms(ref["first"], p0)
    med = float(np.median(ref1))
    keep = [r >= MOVED * med for r in ref1]
    pairs = {"change_gap": (prog["last"], ref["last"])}
    if prog.get("first") is not None:
        pairs["update1_gap"] = (prog["first"], ref["first"])
    out = {}
    for key, (a, b) in pairs.items():
        gaps = leaf_gaps(leaf_norms(a, p0), leaf_norms(b, p0), keep)
        order = np.argsort(gaps)[::-1][:top]
        out[key] = [(*names[i], gaps[i]) for i in order]
    return out


def support_mismatch(p0, prog_last, ref_last) -> float:
    """Of the entries that either side's global moved from ``p0`` over the
    rounds compared (under a top-k uplink, the union of the entries every
    client sent), the share that only one side moved."""
    both = either = 0
    for a, b, z in zip(jax.tree.leaves(prog_last), jax.tree.leaves(ref_last),
                       jax.tree.leaves(p0)):
        z = np.asarray(z, np.float64)
        pa, pb = np.asarray(a, np.float64) != z, np.asarray(b, np.float64) != z
        both += int(np.sum(pa & pb))
        either += int(np.sum(pa | pb))
    return 1.0 - both / either
