"""The comparison that decides ``correct``: the program's set-up rounds
against the plain reference over the same rows and selections.

The numbers (each cell holds those its ``cells/<workload>.json`` gives a
limit, and prints them beside their limits):

* ``sel_rows`` — selection rows that are not 0/1 with exactly the
  traffic's number of trained units (an exact check, limit 0);
* ``loss`` — the widest gap between a client's round loss and the
  reference's, over every client of the three rounds, relative to the
  reference's;
* ``loss_r1`` — the gap of round 1's loss (the mean over its clients),
  relative: the first step's loss;
* ``update`` — the worst leaf's gap between the norm of the program's
  round-1 update (weights after round 1 minus the initial weights: what
  the server's optimizer, FedAvg, applies) and the reference's;
* ``change`` — the same for the change over all three rounds;
* ``update_median``, ``change_median`` — the median leaf's gap instead
  of the worst;
* ``update_weights`` — the worst gap of ``update`` among the weight
  kernels and matrices (leaves of two or more axes), leaving out the
  vectors (biases, batch-norm scales and shifts);
* ``feed_rows`` — rows the program's loader fed that are not among
  their client's own rows, or that repeat within a client's round (an
  exact check, limit 0; counted by ``harness.reference_feed``).

A leaf's gap is taken against the reference's norm of that leaf or of
the median leaf, whichever is larger.  Leaves whose reference gradient
at the initial weights is under a thousandth of the median leaf's move
under Adam by round-off alone (the biases before batch normalisation)
or not at all (unread moving statistics); they are left out of both.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

GRAD_FLOOR = 1e-3


def _norms(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]):
    return {k: float(np.linalg.norm((a[k] - b[k]).ravel())) for k in b}


def kept_leaves(grad_norms: Dict[str, float]) -> List[str]:
    med = float(np.median(list(grad_norms.values())))
    return sorted(k for k, v in grad_norms.items() if v >= GRAD_FLOOR * med)


def leaf_gaps(obs: Dict[str, float], ref: Dict[str, float],
              kept: List[str]) -> Dict[str, float]:
    """Each kept leaf's norm gap, against the larger of its reference
    norm and the median leaf's."""
    med = float(np.median([ref[k] for k in kept]))
    return {k: abs(obs[k] - ref[k]) / max(ref[k], med, 1e-30) for k in kept}


def readings(obs: Dict[str, Any], ref: Dict[str, Any], feed, n_train: int,
             detail: bool = False) -> Dict[str, Any]:
    """The compared numbers of one run; with ``detail``, also the gap of
    every round and every kept leaf behind them."""
    bad = 0
    for rnd in feed:
        s = np.asarray(rnd["sel"])
        bad += int(np.sum(~np.all((s == 0) | (s == 1), axis=1)
                          | (s.sum(axis=1) != n_train)))
    gaps = [np.max(np.abs(o - r) / np.abs(r))
            for o, r in zip(obs["losses"], ref["losses"])]
    kept = kept_leaves(ref["grad0_norms"])
    p0 = ref["p0"]
    update = leaf_gaps(_norms(obs["p1"], p0), _norms(ref["p1"], p0), kept)
    change = leaf_gaps(_norms(obs["p_last"], p0), _norms(ref["p_last"], p0),
                       kept)
    means = [float(abs(np.mean(o) - np.mean(r)) / abs(np.mean(r)))
             for o, r in zip(obs["losses"], ref["losses"])]
    weights = [update[k] for k in kept if p0[k].ndim >= 2]
    out = {"sel_rows": float(bad), "feed_rows": float(ref["feed_rows"]),
           "loss": float(max(gaps)), "loss_r1": means[0],
           "update": max(update.values()), "change": max(change.values()),
           "update_weights": max(weights, default=float("nan")),
           "update_median": float(np.median(list(update.values()))),
           "change_median": float(np.median(list(change.values())))}
    if detail:
        out["detail"] = {
            "loss_rounds": [float(g) for g in gaps],
            "mean_loss_rounds": means,
            "update_leaves": update, "change_leaves": change,
            "left_out": sorted(set(p0) - set(kept))}
    return out


def checks(obs, ref, feed, n_train: int, limits: Dict[str, Any]
           ) -> Dict[str, Dict[str, Any]]:
    """The numbers the cell holds, each beside its limit."""
    r = readings(obs, ref, feed, n_train)
    return {k: {"value": r[k], "limit": v} for k, v in limits.items()}


def passed(checks: Dict[str, Dict[str, Any]]) -> bool:
    """Every number within its limit (a missing limit fails)."""
    return bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


def lines(checks: Dict[str, Dict[str, Any]]) -> List[str]:
    return [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
