"""End-to-end FEEL training (the paper's own experiment, §VI).

Trains the paper's CNN on the synthetic MNIST-like dataset with 10%
mislabeling, K=10 devices (one class each), N=5 RBs, Q=2 — the full
Algorithm-1 loop with wireless costs, availability, selection and
IPW aggregation.  Compare --scheme proposed vs baseline1..baseline4.

    PYTHONPATH=src python examples/feel_e2e.py --rounds 150
"""
import argparse
import json
import sys

import jax
import numpy as np

from repro import obs
from repro.fed import (CHAOS_SPEC, FEELTrainer, FaultSpec, ResilienceConfig,
                       paper_setup)
from repro.launch.compile_cache import enable_compile_cache


def parse_faults(arg):
    """--faults chaos | --faults '{"seed": 1, "dropout_prob": 0.2}'."""
    if arg is None:
        return None
    if arg == "chaos":
        return CHAOS_SPEC
    return FaultSpec.from_dict(json.loads(arg))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--scheme", default="proposed")
    ap.add_argument("--mislabel", type=float, default=0.1)
    ap.add_argument("--d-hat", type=int, default=60)
    ap.add_argument("--side", type=int, default=20)
    ap.add_argument("--selection", default="faithful",
                    choices=["faithful", "exact"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a repro.obs JSONL telemetry trace "
                         "(per-round stage timings, solver counters, "
                         "per-device energy) and print its summary")
    ap.add_argument("--dash", default=None, metavar="PATH",
                    help="with --trace: also render the trace as a "
                         "self-contained HTML round dashboard at PATH "
                         "(same as `python -m repro.obs dash`)")
    ap.add_argument("--monitor", action="store_true",
                    help="attach a ConvergenceMonitor checking each round "
                         "against the paper's Lemma-2 bound; print its "
                         "summary (violations go to --trace if given)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="install a process-wide metrics registry and "
                         "write its Prometheus exposition to PATH")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="inject faults: 'chaos' for the aggressive "
                         "preset, or a FaultSpec JSON object "
                         "(docs/robustness.md)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="directory for periodic trainer checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    metavar="N", help="checkpoint every N rounds")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the checkpoint in --checkpoint-dir "
                         "before running")
    ap.add_argument("--check-resume", action="store_true",
                    help="self-test: run to completion, then replay the "
                         "second half from a mid-run checkpoint with a "
                         "fresh trainer and assert bit-identical params "
                         "(exits non-zero on mismatch)")
    args = ap.parse_args()
    enable_compile_cache()
    faults = parse_faults(args.faults)

    sys_, data, model, params, cfg = paper_setup(
        side=args.side, d_hat=args.d_hat, scheme=args.scheme,
        selection=args.selection, mislabel=args.mislabel)
    tele = None
    if args.trace:
        tele = obs.Telemetry(path=args.trace,
                             meta={"source": "examples.feel_e2e",
                                   "scheme": args.scheme,
                                   "rounds": args.rounds})
    reg = None
    if args.metrics:
        reg = obs.Registry()
        obs.metrics.set_default(reg)
    monitor = None
    if args.monitor:
        monitor = obs.ConvergenceMonitor(sys_, telemetry=tele, registry=reg)

    resilience = None
    if (faults is not None or args.checkpoint_every or args.checkpoint_dir
            or args.check_resume):
        resilience = ResilienceConfig(checkpoint_every=args.checkpoint_every,
                                      checkpoint_dir=args.checkpoint_dir)

    def make_trainer(res=resilience, quiet=False):
        return FEELTrainer(sys_, data, model, params, cfg,
                           telemetry=None if quiet else tele,
                           monitor=None if quiet else monitor,
                           faults=faults, resilience=res)

    trainer = make_trainer()
    if args.resume:
        start = trainer.resume()
        print(f"resumed from round {start}")
    metrics = trainer.run(args.rounds, verbose=True)
    final = [m for m in metrics if m.test_acc is not None][-1]

    if args.check_resume:
        import tempfile
        half = max(args.rounds // 2, 1)
        with tempfile.TemporaryDirectory() as tmp:
            # threshold 1: any surviving NaN upload quarantines, so the
            # chaos run deterministically exercises the quarantine path
            res = ResilienceConfig(checkpoint_every=half,
                                   checkpoint_dir=tmp,
                                   quarantine_threshold=1)
            full = make_trainer(res=res, quiet=True)
            ms_full = full.run(args.rounds)
            partial = make_trainer(res=res, quiet=True)
            partial.run(half)  # writes the checkpoint at round `half`
            resumed = make_trainer(res=res, quiet=True)
            start = resumed.resume()
            resumed.run(args.rounds)
        same = all(np.array_equal(a, b)
                   for a, b in zip(jax.tree.leaves(full.params),
                                   jax.tree.leaves(resumed.params)))
        ok_finite = all(bool(np.isfinite(np.asarray(x)).all())
                        for x in jax.tree.leaves(full.params))
        n_quar = sum(m.n_quarantined for m in ms_full)
        print(f"\ncheck-resume: resumed_at={start} bit_identical={same} "
              f"finite={ok_finite} quarantined_device_rounds={n_quar}")
        if not (same and ok_finite):
            print("check-resume FAILED", file=sys.stderr)
            raise SystemExit(1)
        if faults is not None and faults.nan_prob > 0 and n_quar == 0:
            print("check-resume FAILED: chaos plan injected NaN uploads "
                  "but quarantine never triggered", file=sys.stderr)
            raise SystemExit(1)
    print(f"\nFINAL: acc={final.test_acc:.3f} "
          f"cum_net_cost={final.cum_net_cost:+.3f}")
    if tele is not None:
        tele.close()
        print(f"\ntelemetry trace -> {args.trace}")
        print("name,us_per_call,derived")
        obs.emit_summary(obs.summarize(tele.events))
        if args.dash:
            obs.write_dashboard(args.trace, args.dash)
            print(f"round dashboard -> {args.dash}")
        print(f"inspect: python -m repro.obs export {args.trace}  "
              f"(Perfetto), ... diff, ... dash")
    if monitor is not None:
        s = monitor.summary()
        print(f"\nmonitor: rounds={s['rounds']} "
              f"bound_gap_ratio={s['bound_gap_ratio']:.3f} "
              f"violations={s['violations'] or '{}'}")
    if reg is not None:
        obs.metrics.set_default(None)
        with open(args.metrics, "w") as f:
            f.write(reg.render())
        print(f"metrics exposition -> {args.metrics}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump([{k: v for k, v in m.__dict__.items()
                        if k != "decision"} for m in metrics], f)


if __name__ == "__main__":
    main()
