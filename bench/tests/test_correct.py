"""The control and the faults come out as not correct.

* The control: the reference computed in bfloat16, put in the program's
  place, read against the float32 reference under each cell's limits.
* The faults (``faults.py``): a run with the timed path broken
  underneath (the harness's look for a chip skipped): a step that leaves
  the state unchanged, half of the uploads left out with the mean taken
  over the rest, and one answer (a power) altered where it is produced.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import correct
import faults
import inputs
import reference
import run as bench_run
from conftest import BENCH, TINY

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _cell(workload):
    cell = bench_run.load_cell(workload, trace=False)
    cell.cfg = {**cell.cfg, **TINY}
    return cell


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    cell = _cell(workload)
    sub = inputs.seeds(11)
    data = cell.model.samples(cell.cfg, sub["data"])
    params0 = cell.model.init_params(cell.cfg, sub["weights"])
    ctl = reference.Reference(cell.cfg, cell.traffic, sub, cell.model,
                              dtype=jnp.bfloat16, precision=None)
    got = calibrate.as_observed(ctl.run(data, params0))
    ref = reference.Reference(cell.cfg, cell.traffic, sub, cell.model).run(
        data, params0, selections=got.delta)
    ok, _ = correct.judge(correct.compare(got, ref), correct.limits(workload))
    assert not ok


def _run(workload):
    return bench_run.run(workload, 5, 0.2, False, require_chip=False,
                         cfg_changes=TINY, limits=correct.limits(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(workload, fault):
    with faults.FAULTS[fault]():
        out = _run(workload)
    assert out["correct"] is False, out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
    assert np.isfinite([c["value"] for c in out["checks"].values()]).all()
