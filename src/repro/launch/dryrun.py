"""Multi-pod dry run: lower + compile every (arch x shape) on the
production meshes, extract cost/memory/collective analyses, and append
one JSON record per combination to experiments/dryrun.jsonl.

Usage:
    python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
    python -m repro.launch.dryrun --all                 # single-pod sweep
    python -m repro.launch.dryrun --all --multi-pod     # 512-chip sweep

``main`` appends --xla_force_host_platform_device_count=512 to
XLA_FLAGS before jax initializes its backends (jax fixes the device
count then); importing the module leaves XLA_FLAGS alone.
"""
import argparse
import json
import os
import re
import time
import traceback
from typing import Dict

import jax

from ..configs import ARCHS
from . import mesh as mesh_mod
from . import sharding as sh
from .shapes import SHAPES, applicable, build_spec

# TPU v5e constants (per chip)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s/link

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "f16": 2, "bf16": 2, "s16": 2, "u16": 2, "s8": 1,
                "u8": 1, "pred": 1, "f8e4m3": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op, by op kind.

    Token-search based: the defining line looks like
        %name = SHAPE op-name(...)   or   ... op-name-start(...)
    (a regex with a greedy shape class backtracks "all-reduce" into
    "-reduce" and silently drops single-output collectives — found the
    hard way; the async "-done" retrievals are intentionally skipped
    so started collectives aren't double-counted)."""
    out = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        _, _, rhs = line.partition(" = ")
        rhs = " " + rhs  # shape may start the segment
        for c in _COLLECTIVES:
            pos = rhs.find(f" {c}(")
            if pos < 0:
                pos = rhs.find(f" {c}-start(")
            if pos >= 0:
                out[c] += _shape_bytes(rhs[:pos])
                out["count"] += 1
                break
    return out


def _compile_metrics(spec) -> dict:
    """Lower + compile one spec; return raw per-device metrics."""
    donate = {"train": (0, 1), "prefill": (), "decode": (1,)}[spec.kind]
    t0 = time.time()
    lowered = jax.jit(spec.step_fn, donate_argnums=donate).lower(*spec.args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_rec = {"error": str(e)}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "memory": mem_rec,
        "hlo_lines": hlo.count("\n"),
        "t_lower": t_lower,
        "t_compile": t_compile,
    }


def run_one(arch: str, shape: str, multi_pod: bool, feel: bool = True,
            mla_absorbed: bool = False, variant: str = "baseline",
            out_path: str = "experiments/dryrun.jsonl",
            cfg_overrides: dict | None = None,
            strategy: str = "tp") -> dict:
    """Lower + compile (arch x shape) on the production mesh.

    cost_analysis counts a lax.scan body ONCE regardless of trip count,
    so we compile at scan_unroll=1 and scan_unroll=2 and extrapolate
    the affine law F(u) = outside + u*body to the true layer count
    (validated within 0.4% FLOPs / 4% bytes of a full unroll on
    llama3.2-3b; the scan program is also what production executes).
    """
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape,
           "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
           "multi_pod": multi_pod, "variant": variant, "feel": feel,
           "mla_absorbed": mla_absorbed, "strategy": strategy, "ok": False}
    t0 = time.time()
    try:
        from ..models.transformer import _layer_plan
        spec1 = build_spec(arch, shape, mesh, feel=feel,
                           mla_absorbed=mla_absorbed, scan_unroll=1,
                           cfg_overrides=cfg_overrides, strategy=strategy)
        _, n_body, _, _ = _layer_plan(spec1.cfg)
        with mesh, sh.with_mesh_constraints(mesh, strategy):
            m1 = _compile_metrics(spec1)
            if n_body >= 2:
                spec2 = build_spec(arch, shape, mesh, feel=feel,
                                   mla_absorbed=mla_absorbed,
                                   scan_unroll=2,
                                   cfg_overrides=cfg_overrides,
                                   strategy=strategy)
                m2 = _compile_metrics(spec2)
            else:
                m2 = None

        def extrap(v1, v2):
            if m2 is None:
                return v1
            body = max(v2 - v1, 0.0)
            return max(v1 - body, 0.0) + n_body * body

        flops = extrap(m1["flops"], m2["flops"] if m2 else 0.0)
        bytes_acc = extrap(m1["bytes"], m2["bytes"] if m2 else 0.0)
        coll = {c: int(extrap(m1["coll"][c], m2["coll"][c] if m2 else 0))
                for c in _COLLECTIVES}
        coll["count"] = m1["coll"]["count"]
        coll_total = sum(coll[c] for c in _COLLECTIVES)
        rec.update(
            ok=True, n_body=n_body,
            t_lower_s=round(m1["t_lower"], 2),
            t_compile_s=round(m1["t_compile"]
                              + (m2["t_compile"] if m2 else 0.0), 2),
            flops_per_device=flops, bytes_per_device=bytes_acc,
            collective_bytes_per_device=coll_total,
            collectives=coll, memory=m1["memory"],
            raw_scan_flops=m1["flops"],
            hlo_lines=m1["hlo_lines"],
            compute_term_s=flops / PEAK_FLOPS,
            memory_term_s=bytes_acc / HBM_BW,
            collective_term_s=coll_total / ICI_BW,
        )
        terms = {"compute": rec["compute_term_s"],
                 "memory": rec["memory_term_s"],
                 "collective": rec["collective_term_s"]}
        rec["bottleneck"] = max(terms, key=terms.get)

        # MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active
        cfg = spec1.cfg
        import jax.tree_util as jtu
        total = active = 0
        for path, leaf in jtu.tree_flatten_with_path(spec1.args[0])[0]:
            keys = [str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path]
            n = 1
            for s in leaf.shape:
                n *= s
            total += n
            is_expert = (cfg.n_experts > 0 and leaf.ndim >= 3
                         and cfg.n_experts in leaf.shape
                         and keys[-1] in ("w_gate", "w_up", "w_down")
                         and "shared" not in keys)
            active += int(n * cfg.topk / cfg.n_experts) if is_expert else n
        info = SHAPES[shape]
        D = info["batch"] * (info["seq"] if spec1.kind != "decode" else 1)
        mult = 6 if spec1.kind == "train" else 2
        model_flops = mult * active * D / mesh.size
        rec.update(params_total=int(total), params_active=int(active),
                   model_flops_per_device=model_flops,
                   useful_ratio=(model_flops / flops) if flops else None)
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["t_total_s"] = round(time.time() - t0, 2)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "a") as f:
        json.dump(rec, f)
        f.write("\n")
    return rec


def main():
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS + ["all"], default=None)
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-feel", action="store_true")
    ap.add_argument("--mla-absorbed", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--out", default="experiments/dryrun.jsonl")
    args = ap.parse_args()

    archs = ARCHS if (args.all or args.arch in (None, "all")) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape in (None, "all")) \
        else [args.shape]

    for arch in archs:
        for shape in shapes:
            if not applicable(arch, shape):
                print(f"SKIP  {arch} x {shape} (sub-quadratic gate, "
                      "see DESIGN.md)")
                continue
            rec = run_one(arch, shape, args.multi_pod,
                          feel=not args.no_feel,
                          mla_absorbed=args.mla_absorbed,
                          variant=args.variant, out_path=args.out,
                          strategy=args.strategy)
            status = "OK  " if rec["ok"] else "FAIL"
            extra = (f"flops/dev={rec.get('flops_per_device', 0):.3g} "
                     f"bottleneck={rec.get('bottleneck')}"
                     if rec["ok"] else rec.get("error", ""))
            print(f"{status} {arch:>20s} x {shape:<12s} mesh={rec['mesh']} "
                  f"t={rec['t_total_s']}s {extra}", flush=True)


if __name__ == "__main__":
    main()
