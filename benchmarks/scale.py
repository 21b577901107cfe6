"""Solver scaling benchmark: K in {8, 64, 256, 1024} devices.

Times the per-round decision stack — swap matching (Alg. 2), the final
power solve, CCP power (Alg. 3, bucketed inner solve) and data
selection (Algs. 4+5) — through the existing telemetry stages, for the
batched solver paths and (where affordable) the historical scalar
sweep:

    PYTHONPATH=src python -m benchmarks.scale                    # gate
    PYTHONPATH=src python -m benchmarks.scale --update-baseline
    PYTHONPATH=src python -m benchmarks.scale --check            # 5x
    PYTHONPATH=src python -m benchmarks.scale --ks 64 --trace t.jsonl

Modes (see docs/solvers.md):

* ``batched`` — vectorized sweep scoring every candidate move of a
  device in one closed-form evaluation (``core.matching._BatchScorer``)
  plus the chunked gradient projection; decisions are identical to the
  scalar path (tests/test_solver_equivalence.py), so only wall-clock
  differs.
* ``scalar`` — the per-candidate Python loop, run up to
  ``SCALAR_MAX_K`` devices (it is what the batched path is measured
  against; beyond that it is minutes per round).

CCP is benchmarked up to ``CCP_MAX_K`` on a fresh sparsity pattern per
rep, so its p50 reflects the bucketed retrace-free steady state, not
compilation.

``--check`` enforces the PR-10 acceptance bar: at K=256/N=32 the
batched matching+power+selection stages complete >= 5x faster than the
scalar path AND both modes return identical assignments.  The default
(gate) mode compares batched p50s against the committed
``benchmarks/baselines/BENCH_scale.json`` like benchmarks/regress.py —
latency growth past tolerance fails, faster always passes (CI runs it
non-blocking).
"""
from __future__ import annotations

import argparse
import json
import os
import sys as _sys
from typing import Dict, List, Optional

import numpy as np

BASELINE = os.path.join(os.path.dirname(__file__), "baselines",
                        "BENCH_scale.json")

#: stages whose p50s the baseline tracks and ``--check`` sums.
STAGES = ("matching", "power", "selection")
KS_DEFAULT = (8, 64, 256, 1024)
#: largest K the scalar reference sweep is run at (O(K^2) Python calls
#: per sweep — beyond this it is minutes per round).
SCALAR_MAX_K = 256
#: largest K the CCP benchmark runs at (the Newton system is dense in
#: the K active variables).
CCP_MAX_K = 64
CONFIG = {"N": 32, "J": 50, "gp_steps": 100, "reps": 3, "seed": 0}


def _make_instance(K: int, rep: int, rng: np.random.Generator):
    """One round's (sys, h, alpha, sigma) at N=32, capacity == K."""
    import jax.numpy as jnp

    from repro.core import default_system

    N = CONFIG["N"]
    sys_ = default_system(K=K, N=N, Q=max(1, -(-K // N)))
    h = rng.gamma(2.0, 1e-5, size=(K, N))
    sigma = jnp.asarray(rng.gamma(2.0, 1.0, size=(K, CONFIG["J"])),
                        jnp.float32)
    alpha = np.ones(K)
    return sys_, h, alpha, sigma


def _stage_p50s(tele) -> Dict[str, float]:
    """Per-stage p50 latencies (ms), rep 0 (jit warmup) excluded."""
    from repro import obs

    durs: Dict[str, List[float]] = {}
    for e in tele.events:
        if isinstance(e, obs.StageEvent) and (e.round or 0) >= 1:
            durs.setdefault(e.stage, []).append(e.dur_s)
    return {name: float(np.percentile(v, 50) * 1e3)
            for name, v in sorted(durs.items())}


def bench_k(K: int, mode: str, reps: int,
            trace_path: Optional[str] = None) -> Dict:
    """Time ``reps + 1`` decision rounds at K devices in one mode.

    Returns stage p50s (warmup rep excluded), the summed
    matching+power+selection p50 total, solver counters, and the final
    rep's assignment (for the equivalence check).
    """
    import jax.numpy as jnp

    from repro import obs
    from repro.core import matching as matching_mod
    from repro.core import selection as selection_mod

    tele = obs.Telemetry(path=trace_path,
                         meta={"source": "benchmarks.scale", "K": K,
                               "mode": mode, "config": dict(CONFIG)})
    rng = np.random.default_rng(CONFIG["seed"])
    assign = None
    swaps = sweeps = rb_evals = 0
    try:
        for rep in range(reps + 1):
            tele.begin_round(rep)
            sys_, h, alpha, sigma = _make_instance(K, rep, rng)
            match = matching_mod.swap_matching(sys_, h, alpha, mode=mode,
                                               telemetry=tele)
            with tele.stage("selection"):
                tele.block(selection_mod.solve_selection(
                    sys_, sigma, jnp.ones_like(sigma),
                    steps=CONFIG["gp_steps"], telemetry=tele))
            assign = match.assign
            swaps, sweeps = match.swaps, match.sweeps
    finally:
        tele.close()
    p50s = _stage_p50s(tele)
    total = sum(p50s.get(s, 0.0) for s in STAGES)
    return {"stages": {s: p50s[s] for s in p50s if s in STAGES},
            "total_ms": total, "swaps": swaps, "sweeps": sweeps,
            "assign": assign}


def bench_ccp(K: int, reps: int) -> float:
    """p50 of the bucketed CCP solve over fresh sparsity patterns.

    Every rep re-matches a fresh channel draw, so each solve sees a new
    (k, n) active set — with bucketing these hit the cached Newton
    step, which is exactly the steady state the baseline should track.
    The first rep (compilation) is excluded.
    """
    import time

    import jax.numpy as jnp

    from repro.core import matching as matching_mod
    from repro.core import power as power_mod

    rng = np.random.default_rng(CONFIG["seed"] + 1)
    durs = []
    for rep in range(reps + 1):
        sys_, h, alpha, _ = _make_instance(K, rep, rng)
        match = matching_mod.swap_matching(sys_, h, alpha, mode="auto")
        t0 = time.perf_counter()
        power_mod.allocate_power(sys_, jnp.asarray(match.rho),
                                 jnp.asarray(h, jnp.float32),
                                 jnp.asarray(alpha, jnp.float32),
                                 method="ccp")
        if rep > 0:
            durs.append(time.perf_counter() - t0)
    return float(np.percentile(durs, 50) * 1e3)


def run_sweep(ks, reps: int, trace_path: Optional[str] = None,
              with_scalar: bool = True) -> Dict:
    sweep = {}
    for K in ks:
        rec: Dict = {}
        batched = bench_k(K, "batched", reps, trace_path=trace_path)
        assign_b = batched.pop("assign")
        rec["batched"] = batched
        if with_scalar and K <= SCALAR_MAX_K:
            scalar = bench_k(K, "scalar", reps)
            assign_s = scalar.pop("assign")
            rec["scalar"] = scalar
            rec["speedup"] = (scalar["total_ms"]
                              / max(batched["total_ms"], 1e-9))
            rec["decisions_equal"] = bool(
                np.array_equal(assign_b, assign_s))
        else:
            print(f"K={K}: scalar reference skipped "
                  f"(> SCALAR_MAX_K={SCALAR_MAX_K})")
        if K <= CCP_MAX_K:
            rec["ccp_p50_ms"] = bench_ccp(K, reps)
        line = (f"K={K}: batched {batched['total_ms']:.1f}ms"
                + (f", scalar {rec['scalar']['total_ms']:.1f}ms "
                   f"({rec['speedup']:.1f}x, decisions_equal="
                   f"{rec['decisions_equal']})" if "scalar" in rec else "")
                + (f", ccp {rec['ccp_p50_ms']:.1f}ms"
                   if "ccp_p50_ms" in rec else ""))
        print(line)
        sweep[str(K)] = rec
    return {"bench": "scale", "config": dict(CONFIG), "sweep": sweep}


def compare(cur: Dict, base: Dict, latency_tol: float = 2.0) -> List[str]:
    """Regression messages for the Ks present in the current run."""
    fails: List[str] = []
    if cur.get("config") != base.get("config"):
        return [f"config changed ({cur.get('config')} vs baseline "
                f"{base.get('config')}) — rerun with --update-baseline"]
    for K, c in cur.get("sweep", {}).items():
        b = base.get("sweep", {}).get(K)
        if b is None:
            fails.append(f"K={K} missing from baseline — rerun with "
                         f"--update-baseline")
            continue
        cb, bb = c["batched"], b["batched"]
        # floor scales with K: micro-stage jitter at K=8 must not flap
        floor = 1.0 + 0.01 * float(K)
        if cb["total_ms"] > bb["total_ms"] * latency_tol + floor:
            fails.append(f"K={K} batched total: {cb['total_ms']:.1f}ms > "
                         f"{latency_tol:g}x baseline "
                         f"{bb['total_ms']:.1f}ms")
        for cnt in ("swaps", "sweeps"):
            if cb[cnt] > bb[cnt]:
                fails.append(f"K={K} {cnt}: {cb[cnt]} > baseline "
                             f"{bb[cnt]} (deterministic per seed)")
        if b.get("decisions_equal") and not c.get("decisions_equal", True):
            fails.append(f"K={K}: batched and scalar assignments diverged")
    return fails


def check_acceptance(reps: int) -> List[str]:
    """The PR-10 bar: >=5x at K=256/N=32 with identical decisions."""
    rec = run_sweep([256], reps)["sweep"]["256"]
    fails = []
    if not rec.get("decisions_equal"):
        fails.append("K=256: batched and scalar assignments diverged")
    if rec.get("speedup", 0.0) < 5.0:
        fails.append(f"K=256: speedup {rec.get('speedup', 0):.2f}x < 5x")
    return fails


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ks", type=int, nargs="+", default=list(KS_DEFAULT))
    ap.add_argument("--reps", type=int, default=CONFIG["reps"])
    ap.add_argument("--out", default="BENCH_scale.json")
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the baseline instead of comparing")
    ap.add_argument("--check", action="store_true",
                    help="enforce the >=5x @ K=256 acceptance bar")
    ap.add_argument("--no-scalar", action="store_true",
                    help="skip the scalar reference sweeps")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the batched runs' telemetry JSONL trace")
    ap.add_argument("--latency-tol", type=float, default=2.0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.check:
        fails = check_acceptance(args.reps)
        for msg in fails:
            print(f"CHECK FAILED: {msg}", file=_sys.stderr)
        if fails:
            _sys.exit(1)
        print("PASS: batched solver >= 5x scalar at K=256/N=32 with "
              "identical decisions")
        return

    cur = run_sweep(args.ks, args.reps, trace_path=args.trace,
                    with_scalar=not args.no_scalar)
    with open(args.out, "w") as f:
        json.dump(cur, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")

    if args.update_baseline:
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(cur, f, indent=1, sort_keys=True)
        print(f"baseline refreshed -> {args.baseline}")
        return
    if not os.path.exists(args.baseline):
        print(f"no baseline at {args.baseline}; run with "
              f"--update-baseline to create one", file=_sys.stderr)
        _sys.exit(2)
    with open(args.baseline) as f:
        base = json.load(f)
    fails = compare(cur, base, latency_tol=args.latency_tol)
    for msg in fails:
        print(f"REGRESSION: {msg}", file=_sys.stderr)
    if fails:
        _sys.exit(1)
    print(f"PASS: no regression vs {args.baseline} "
          f"({len(cur['sweep'])} configs)")


if __name__ == "__main__":
    main()
