"""What every model's inputs share: the run's sub-seeds from ``--seed``,
the per-device samples under neutral names, and the paper's label noise.

A model module (``bench/models/<model>.py``) makes its own data and
weights from the ``data`` and ``weights`` sub-seeds; the ``rounds``
sub-seed drives the trainer's and the reference's draws.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Samples:
    """Per-device shards: device k holds ``x[k][j]`` with label
    ``labels[k][j]``."""

    x: List[np.ndarray]        # K x (per_device, ...) model inputs
    labels: List[np.ndarray]   # labels as seen (some corrupted), int32
    true: List[np.ndarray]     # ground-truth labels, int32


def seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit sub-seeds for data, weights and rounds."""
    words = np.random.SeedSequence(int(seed)).generate_state(3, np.uint32)
    return {name: int(w) & 0x7FFFFFFF
            for name, w in zip(("data", "weights", "rounds"),
                               words)}


def mislabel(labels: np.ndarray, share: float, classes: int,
             seed: int) -> np.ndarray:
    """``labels`` with a ``share`` of them replaced by a label drawn
    uniformly from the other classes."""
    rng = np.random.default_rng(seed)
    n_bad = int(round(share * labels.shape[0]))
    idx = rng.choice(labels.shape[0], size=n_bad, replace=False)
    out = labels.copy()
    if n_bad:
        out[idx] = (labels[idx] + rng.integers(1, classes, n_bad)) % classes
    return out
