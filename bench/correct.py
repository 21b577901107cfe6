"""The numbers that decide ``correct``: what the timed path produced in
the run's first rounds against the plain reference (``reference.py``).
A cell's ``limits/<cell>.json`` names the numbers it compares, each with
the limit set from chip readings (PERF.md); the others are reported.

The reference takes its gradients over the program's selection, as a
served model's reference is run over the served tokens: ``delta_diff``
holds the selection itself to the reference's own, and the gradient
numbers then see the gradients and the sum, not a sample or two that
the selection took on one side only.

* ``power_gap``: per round and (device, RB) entry, |p - p_ref| over the
  larger of the two; the worst entry. An RB given to another device
  reads 1.
* ``delta_diff``: the share of the first round's selection entries that
  differ from the reference's own. Later rounds' selections follow
  weights that have taken Adam steps, whose first steps are about
  lr·sign(g): an entry whose gradient is near 0 moves by ±lr on
  rounding alone, and the selections of rounds 2 and 3 swing from seed
  to seed (PERF.md).
* ``upload_diff``: over the rounds, how many uploads the aggregation
  counted differently, plus rounds whose update was skipped on one
  side only. Exact: its limit is 0.
* ``grad_gap``: the first applied g_hat as Adam holds it after one step
  (mu / (1 - b1)) against the reference's. Per leaf, the gap between
  the two norms over the larger of the reference leaf's norm and the
  median leaf norm; the worst leaf.
* ``update_gap``: the same measure for the parameters' change over the
  rounds. Leaves whose reference gradient is under a thousandth of the
  median leaf's are left out: Adam moves them by round-off alone.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional

import jax
import numpy as np

NUMBERS = ("power_gap", "delta_diff", "upload_diff",
           "grad_gap", "update_gap")
LIMITS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "limits")


@dataclasses.dataclass
class Observed:
    """What the program produced in its first rounds."""

    rho: List[np.ndarray]
    p: List[np.ndarray]
    delta: List[np.ndarray]
    n_uploaded: List[int]
    skipped: List[bool]
    first_grad: Optional[dict]   # mu / (1 - b1) after the first step
    params0: dict
    params: dict                 # after the last observed round


def _norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64))) for k, v in flat}


def leaf_gap(got, want, keep=None) -> float:
    """Worst leaf: |‖got‖ - ‖want‖| / max(‖want‖, median leaf norm)."""
    g, w = _norms(got), _norms(want)
    floor = float(np.median(list(w.values())))
    gaps = [abs(g[k] - w[k]) / max(w[k], floor, 1e-30)
            for k in w if keep is None or k in keep]
    return max(gaps) if gaps else 0.0


def _sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def moved_leaves(ref_grad) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    n = _norms(ref_grad)
    floor = 1e-3 * float(np.median(list(n.values())))
    return {k for k, v in n.items() if v >= floor}


def per_round(obs: Observed, ref) -> Dict[str, List[float]]:
    """Each round's power, selection and upload readings."""
    out: Dict[str, List[float]] = {"power": [], "delta": [], "uploads": []}
    for i, r in enumerate(ref.rounds):
        p = np.asarray(obs.p[i], np.float64) * (np.asarray(obs.rho[i]) > 0)
        big = np.maximum(np.abs(p), np.abs(r.p))
        on = big > 0
        out["power"].append(float(np.max(np.abs(p - r.p)[on] / big[on]))
                            if on.any() else 0.0)
        out["delta"].append(float(np.mean(
            (np.asarray(obs.delta[i]) > 0.5) != r.delta)))
        out["uploads"].append(float(
            abs(int(obs.n_uploaded[i]) - r.n_uploaded)
            + int(bool(obs.skipped[i]) != r.skipped)))
    return out


def compare(obs: Observed, ref) -> Dict[str, float]:
    """The numbers of one run (``ref``: a ``reference.Trajectory`` over
    ``obs.delta``)."""
    rounds = per_round(obs, ref)
    power = max(rounds["power"])
    delta, uploads = rounds["delta"][0], sum(rounds["uploads"])
    ref_grad = next((r.g_hat for r in ref.rounds if r.g_hat is not None),
                    None)
    if ref_grad is None or obs.first_grad is None:
        grad = update = 0.0 if (ref_grad is None) == (
            obs.first_grad is None) else 1.0
    else:
        grad = leaf_gap(obs.first_grad, ref_grad)
        update = leaf_gap(_sub(obs.params, obs.params0),
                          _sub(ref.params, ref.params0),
                          keep=moved_leaves(ref_grad))
    return {"power_gap": power, "delta_diff": delta,
            "upload_diff": float(uploads), "grad_gap": grad,
            "update_gap": update}


def limits(workload: str) -> Dict[str, float]:
    """The cell's limits, from ``limits/<workload>.json``: the numbers
    it compares, each with its limit."""
    with open(os.path.join(LIMITS_DIR, f"{workload}.json")) as f:
        table = json.load(f)
    unknown = set(table) - set(NUMBERS)
    if unknown:
        raise ValueError(f"{workload}: unknown numbers {sorted(unknown)}")
    return {k: float(v) for k, v in table.items()}


def judge(numbers: Dict[str, float], lims: Dict[str, float]):
    """``(correct, checks)``: each compared number beside its limit."""
    checks = {k: {"value": numbers[k], "limit": lims[k]} for k in lims}
    ok = all(np.isfinite(numbers[k]) and numbers[k] <= lims[k]
             for k in lims)
    return ok, checks
