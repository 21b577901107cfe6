"""Readings that the limits of ``correct`` are set from (run on the chip).

    python bench/calibrate.py --workload paper_k10 --seeds 201-213 \\
        --faulted 3 --out chiprun_out/calibrate_paper_k10.jsonl

For each seed, in one process, at the cell's own size, each against the
reference run over its own selection:

* ``program``: the program's first rounds, the lower readings;
* for the first ``--faulted`` seeds: ``control``, the reference in
  bfloat16 at default precision put in the program's place, and each
  fault of ``faults.py`` planted in the program.

Each line of ``--out`` is one seed's readings as JSON; the last lines on
stdout give, per number, the largest program reading and the smallest
reading of the control and of each fault.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import correct  # noqa: E402
import faults  # noqa: E402
import inputs  # noqa: E402
import reference  # noqa: E402
import run as bench_run  # noqa: E402

KINDS = ("control",) + tuple(faults.FAULTS)


def as_observed(traj) -> correct.Observed:
    first = next((r.g_hat for r in traj.rounds if r.g_hat is not None), None)
    return correct.Observed(
        rho=[r.rho for r in traj.rounds], p=[r.p for r in traj.rounds],
        delta=[r.delta for r in traj.rounds],
        n_uploaded=[r.n_uploaded for r in traj.rounds],
        skipped=[r.skipped for r in traj.rounds], first_grad=first,
        params0=traj.params0, params=traj.params)


def _program(cell, sub):
    from repro import obs

    data, params0, trainer = bench_run.build(cell, sub, obs.NULL)
    return data, params0, bench_run.first_rounds(trainer, params0)


def readings(cell, seed: int, faulted: bool) -> dict:
    sub = inputs.seeds(seed)
    t0 = time.perf_counter()
    data, params0, observed = _program(cell, sub)
    runs = {"program": observed}
    if faulted:
        ctl = reference.Reference(cell.cfg, cell.traffic, sub, cell.model,
                                  dtype=jnp.bfloat16, precision=None)
        runs["control"] = as_observed(ctl.run(data, params0,
                                              bench_run.SETUP_ROUNDS))
        for name, fault in faults.FAULTS.items():
            with fault():
                runs[name] = _program(cell, sub)[2]
    out = {"seed": seed}
    for kind, got in runs.items():
        ref = reference.Reference(
            cell.cfg, cell.traffic, sub, cell.model).run(
            data, params0, bench_run.SETUP_ROUNDS, selections=got.delta)
        out[kind] = correct.compare(got, ref)
        out[kind + "_rounds"] = correct.per_round(got, ref)
    out["seconds"] = time.perf_counter() - t0
    return out


def seed_list(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last, inclusive")
    ap.add_argument("--faulted", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload, trace=False)
    bench_run.require_tpu(cell.chips)
    bench_run.enable_compile_cache()
    rows = []
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        for n, seed in enumerate(seed_list(args.seeds)):
            row = readings(cell, seed, faulted=n < args.faulted)
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)
    for k in correct.NUMBERS:
        line = {"number": k,
                "program_max": max(r["program"][k] for r in rows)}
        for kind in KINDS:
            got = [r[kind][k] for r in rows if kind in r]
            if got:
                line[kind + "_min"] = min(got)
        print(json.dumps(line))
    print(json.dumps({"workload": args.workload,
                      "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
