import json
import os

import pytest

import flops
import peaks
from conftest import BENCH


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_cnn_forward_flops_at_28x28():
    # conv1 392,000 + conv2 1,960,000 + fc1 235,200 + fc2 20,160 + out 1,680
    assert flops.cnn_forward_flops(_cfg("paper_cnn_k10")) == 2_609_040


def test_round_flops_counts_scores_and_selected_passes():
    cfg = _cfg("paper_cnn_k10")
    fwd = 2_609_040
    assert flops.round_flops(cfg, 0) == fwd * 10 * 200
    assert flops.round_flops(cfg, 100) == fwd * (10 * 200 + 300)


def test_known_device_peak():
    assert peaks.peak("TPU v5 lite")["flops"] == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v99")
