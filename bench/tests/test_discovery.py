"""A configuration, a traffic mix or a metric added as a file of its own
is found by its name in BENCHMARK.json and run, with no other edit."""
import importlib.util
import json
import os
import shutil
import sys

import correct
from conftest import BENCH, TINY

ROOT = os.path.dirname(BENCH)


def _copy_checkout(dst):
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)


def _load_run(dst, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "copied_run", os.path.join(dst, "bench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, mod)
    spec.loader.exec_module(mod)
    return mod


def test_added_files_are_found_and_run(tmp_path, monkeypatch):
    _copy_checkout(tmp_path)
    bench = tmp_path / "bench"
    with open(bench / "configs" / "paper_cnn_k10.json") as f:
        cfg = json.load(f)
    cfg.update(TINY, K=6)
    (bench / "configs" / "six_devices.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "even_odds.json").write_text(json.dumps(
        {"scheme": "proposed", "eps_odd": 0.5, "eps_even": 0.5}))
    (bench / "metrics" / "rounds_done.py").write_text(
        "def read(ctx):\n    return len(ctx.walls)\n")
    with open(tmp_path / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({"name": "six_devices", "source": "test",
                            "file": "bench/configs/six_devices.json",
                            "reduced": ["K"], "why": "test"})
    spec["workloads"].append({"name": "six_even", "config": "six_devices",
                              "traffic": "even_odds", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "rounds_done", "unit": "rounds",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["six_even"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    run = _load_run(tmp_path, monkeypatch)
    cell = run.load_cell("six_even", trace=False)
    assert cell.cfg["K"] == 6 and cell.traffic["eps_odd"] == 0.5
    out = run.run("six_even", 3, 0.5, False, require_chip=False,
                  limits={k: 1e-2 for k in correct.NUMBERS})
    assert out["metrics"]["rounds_done"]["value"] >= 1
    assert out["metrics"]["round_ms"]["value"] > 0
    assert "round_p95_ms" not in out["metrics"]
    assert out["correct"] is True
