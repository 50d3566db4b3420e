"""The comparisons that decide ``correct``: numbers read from what the
timed path produced against the plain reference, each with its limit
(``limits`` in the configuration's file, set from measured readings).

Training (the first ``checked_steps`` steps of the trainer the window
drives, against :func:`reference.foem_steps` from the same inputs):

* ``fold_mass_gap`` — each token's responsibilities sum to one, so a step
  must add exactly the minibatch's count of each word to that word's φ̂
  row, and the (K,) totals must move by the rows' column sums.  The worst
  relative miss over the steps, words and topics.
* ``loss_gap`` — the steps' training perplexity against the reference's.
* ``first_change_gap`` / ``change_gap`` — norm of φ̂'s change after the
  first step and after the last checked step, against the reference's, by
  the worst leaf (the (W_s, K) rows, the (K,) totals), relative to that
  leaf's reference norm or the median leaf's, whichever is larger.

Serving (a seeded sample of the window's requests, the longest among
them, against :func:`reference.infer_theta`):

* ``theta_gap`` — the largest |θ − θ_ref| over the sample's topic weights.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

Check = Dict[str, float]


def _check(value: float, limit: float) -> Check:
    return {"value": float(value), "limit": float(limit)}


def leaf_gap(prog: Sequence[np.ndarray], ref: Sequence[np.ndarray]) -> float:
    """Worst |‖p‖ − ‖r‖| over leaves, relative to max(‖r‖, median ‖r‖).
    Leaves the reference leaves all but unmoved (under a thousandth of the
    median leaf) are not compared."""
    pn = [float(np.linalg.norm(np.asarray(p, np.float64))) for p in prog]
    rn = [float(np.linalg.norm(np.asarray(r, np.float64))) for r in ref]
    med = float(np.median(rn))
    gaps = [abs(p - r) / max(r, med) for p, r in zip(pn, rn)
            if r >= 1e-3 * med and med > 0]
    return max(gaps) if gaps else float("inf")


def fold_mass_gap(snaps, steps) -> float:
    worst = 0.0
    for s, st in enumerate(steps, start=1):
        (old, old_k), (new, new_k) = snaps[s - 1], snaps[s]
        pos = st.vocab_pos
        d = new[pos].astype(np.float64) - old[pos].astype(np.float64)
        n_w = np.bincount(np.asarray(st.word_ids).ravel(),
                          weights=np.asarray(st.counts, np.float64).ravel(),
                          minlength=len(pos))
        rows = np.abs(d.sum(1) - n_w) / np.maximum(n_w, 1.0)
        K = d.shape[1]
        per_topic = max(float(n_w.sum()) / K, 1.0)
        dk = np.asarray(new_k, np.float64) - np.asarray(old_k, np.float64)
        totals = np.abs(dk - d.sum(0)) / per_topic
        worst = max(worst, float(rows.max()), float(totals.max()))
    return worst


def training(snaps: List[Tuple[np.ndarray, np.ndarray]], ppl: Sequence[float],
             ref: Sequence, steps: Sequence, limits: Dict) -> Dict[str, Check]:
    """``snaps[0]`` is the state before the first step, ``snaps[s]`` the
    program's (rows over the view, totals) after step s; ``ref[s - 1]`` the
    reference's ``(rows, totals, train_ppl)`` after step s."""
    base_rows, base_k = snaps[0]
    loss = max(abs(p - r[2]) / abs(r[2]) for p, r in zip(ppl, ref))

    def change(s):
        rows, k = snaps[s]
        return [rows - base_rows, k - base_k], [ref[s - 1][0] - base_rows,
                                                ref[s - 1][1] - base_k]

    n = len(steps)
    return {
        "fold_mass_gap": _check(fold_mass_gap(snaps, steps),
                                limits["fold_mass_gap"]),
        "loss_gap": _check(loss, limits["loss_gap"]),
        "first_change_gap": _check(leaf_gap(*change(1)),
                                   limits["first_change_gap"]),
        "change_gap": _check(leaf_gap(*change(n)), limits["change_gap"]),
    }


def serving(theta: np.ndarray, theta_ref: np.ndarray,
            limits: Dict) -> Dict[str, Check]:
    gap = float(np.abs(np.asarray(theta, np.float64)
                       - np.asarray(theta_ref, np.float64)).max())
    return {"theta_gap": _check(gap, limits["theta_gap"])}


def all_pass(checks: Dict[str, Check]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
