"""A configuration, a traffic mix, a metric or a model added as a file of
its own is found by its name in BENCHMARK.json and run, with no other
edit."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

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


STUB = os.path.join(os.path.dirname(__file__), "data", "stub_model.py")
#: drives the copied checkout in a process of its own, so that every
#: module of the harness, the model and the limits come from the copy
DRIVE = """
import json, sys
sys.path.insert(0, "bench")
import run
print(json.dumps([run.run("logreg_k6", 3, 0.5, False, require_chip=False),
                  run.run("logreg_k6", 4, 0.5, True, require_chip=False)]))
"""
METRICS = {
    # a plain span nested in the round, opened twice a round
    "draws_ms": "def read(ctx):\n    return ctx.span_ms('round.draws')\n",
    # an attribute of a span nested in the selection stage
    "gp_steps": ("def read(ctx):\n"
                 "    steps = [n.attrs['steps']\n"
                 "             for n in ctx.span_nodes('selection.gp')]\n"
                 "    return sum(steps) / len(steps) if steps else None\n"),
    # a span the program never opens: left out of the result line
    "absent_ms": "def read(ctx):\n    return ctx.span_ms('no.such.span')\n",
}


def _stub_checkout(dst, model="logreg"):
    """A copied checkout with the stub model, its configuration, limits
    and metrics added as new files and entries."""
    _copy_checkout(dst)
    bench = dst / "bench"
    shutil.copy(STUB, bench / "models" / "logreg.py")
    with open(bench / "configs" / "paper_cnn_k10.json") as f:
        cfg = json.load(f)
    for key in ("side", "conv_channels", "conv_kernel", "fc_dims",
                "train_images", "image_noise", "assumed"):
        del cfg[key]
    cfg.update(TINY, model=model, K=6, dim=12, num_classes=4)
    (bench / "configs" / "logreg_tiny.json").write_text(json.dumps(cfg))
    limits = {"power_gap": 1e-4, "delta_diff": 0.02, "upload_diff": 0.0,
              "grad_gap": 0.11, "update_gap": 0.3}
    (bench / "limits" / "logreg_k6.json").write_text(json.dumps(limits))
    for name, text in METRICS.items():
        (bench / "metrics" / f"{name}.py").write_text(text)
    with open(dst / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({"name": "logreg_tiny", "source": "test",
                            "file": "bench/configs/logreg_tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "logreg_k6", "config": "logreg_tiny",
                              "traffic": "clean", "chips": 1,
                              "why": "test"})
    for name in METRICS:
        spec["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "round loop",
            "moves": "round_ms", "workloads": ["logreg_k6"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return cfg, limits


def test_added_model_is_found_and_run(tmp_path):
    cfg, limits = _stub_checkout(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    out = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    untraced, traced = json.loads(out.stdout.strip().splitlines()[-1])
    for res in (untraced, traced):
        assert res["correct"] is True, res["checks"]
        # the limits are the cell's own file's
        assert {k: c["limit"] for k, c in res["checks"].items()} == limits
    assert untraced["metrics"]["round_ms"]["value"] > 0
    assert untraced["metrics"]["setup_s"]["value"] > 0
    got = traced["metrics"]
    assert got["draws_ms"]["value"] > 0
    assert got["gp_steps"]["value"] == cfg["gp_steps"]
    assert "absent_ms" not in got
    # the CNN cell's per-layer metrics read the same spans
    assert got["sigma_ms"]["value"] > 0


def test_unknown_model_exits_with_the_known_ones(tmp_path, monkeypatch):
    _stub_checkout(tmp_path, model="no_such_model")
    run = _load_run(tmp_path, monkeypatch)
    with pytest.raises(SystemExit,
                       match=r"unknown model 'no_such_model'.*"
                             r"known: \['cnn', 'logreg'\]"):
        run.load_cell("logreg_k6", trace=False)
