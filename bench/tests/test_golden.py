"""The reference, now fed its model part by ``bench/models/cnn.py``,
gives at ``TINY`` exactly what it gave with the CNN built into it.

``data/golden_tiny.json`` was recorded as ``record()`` records it, on the
tree before the model moved out of ``reference.py``: per round sigma,
the matching (rho), the powers, the selection and the leaf norms of the
aggregated gradient; the final parameters by a digest of their bytes
and their norms.
"""
import hashlib
import json
import os
import sys

import jax
import numpy as np

import inputs
import reference
import run as bench_run
from conftest import TINY

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "golden_tiny.json")
SEED = 11


def _leaves(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def record() -> dict:
    cell = bench_run.load_cell("paper_k10", trace=False)
    cfg = {**cell.cfg, **TINY}
    sub = inputs.seeds(SEED)
    data = cell.model.samples(cfg, sub["data"])
    params0 = cell.model.init_params(cfg, sub["weights"])
    traj = reference.Reference(cfg, cell.traffic, sub, cell.model).run(
        data, params0, rounds=3)
    out = {"seed": SEED, "rounds": []}
    for r in traj.rounds:
        out["rounds"].append({
            "sigma": np.asarray(r.sigma, np.float64).tolist(),
            "rho": np.asarray(r.rho, np.float64).tolist(),
            "p": np.asarray(r.p, np.float64).tolist(),
            "delta": np.asarray(r.delta, bool).astype(int).tolist(),
            "g_hat_norms": None if r.g_hat is None else {
                k: float(np.linalg.norm(v))
                for k, v in _leaves(r.g_hat).items()}})
    out["params"] = {
        k: {"sha256": hashlib.sha256(
                np.ascontiguousarray(v).tobytes()).hexdigest(),
            "dtype": str(v.dtype), "shape": list(v.shape),
            "norm": float(np.linalg.norm(np.asarray(v, np.float64)))}
        for k, v in _leaves(traj.params).items()}
    return out


def test_reference_matches_golden_record():
    with open(GOLDEN) as f:
        want = json.load(f)
    got = record()
    assert len(got["rounds"]) == len(want["rounds"]) == 3
    for i, (g, w) in enumerate(zip(got["rounds"], want["rounds"])):
        for key in ("sigma", "rho", "p", "delta", "g_hat_norms"):
            assert g[key] == w[key], f"round {i}: {key}"
    assert got["params"] == want["params"]


if __name__ == "__main__":
    json.dump(record(), sys.stdout, indent=1)
