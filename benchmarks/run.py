"""Benchmark harness: one entry per paper table/figure + the roofline
report.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --fast     # skip fig4/5/6
    PYTHONPATH=src python -m benchmarks.run --trace t.jsonl
                          # + record a repro.obs telemetry trace and
                          #   append its telemetry.* rows to the CSV
    PYTHONPATH=src python -m benchmarks.run --metrics m.prom
                          # + install a process-wide metrics registry and
                          #   write its Prometheus exposition at the end
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the multi-round training figures")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: alg1,fig3,lemma3,fig4,"
                         "fig5,fig6,roofline,chaos")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a repro.obs JSONL telemetry trace and "
                         "append its summary rows to the CSV output")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="install a process-wide metrics registry and "
                         "write its Prometheus exposition to PATH")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    tele = None
    reg = None
    if args.trace:
        from repro import obs

        tele = obs.Telemetry(path=args.trace,
                             meta={"source": "benchmarks.run",
                                   "argv": sys.argv[1:]})
        obs.set_default(tele)
    if args.metrics:
        from repro import obs

        reg = obs.Registry()
        obs.metrics.set_default(reg)

    from . import (alg1_latency, chaos, fig3_ccp_convergence,
                   fig4_convergence_cost, fig5_mislabel, fig6_availability,
                   lemma3_bound, roofline)

    benches = [
        ("alg1", alg1_latency.run),
        ("fig3", fig3_ccp_convergence.run),
        ("lemma3", lemma3_bound.run),
        ("roofline", roofline.run),
        ("chaos", chaos.run),
    ]
    if not args.fast:
        benches += [
            ("fig4", fig4_convergence_cost.run),
            ("fig5", fig5_mislabel.run),
            ("fig6", fig6_availability.run),
        ]
    if args.only:
        keep = set(args.only.split(","))
        benches = [b for b in benches if b[0] in keep]

    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        try:
            fn()
        except Exception as e:  # keep the harness going
            failed.append(name)
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)

    if tele is not None:
        from repro import obs

        obs.set_default(None)
        tele.close()
        obs.emit_summary(obs.summarize(tele.events))
        print(f"trace -> {args.trace}; view it with "
              f"`python -m repro.obs export {args.trace}` (Perfetto) or "
              f"`python -m repro.obs dash {args.trace}`", file=sys.stderr)
    if reg is not None:
        from repro import obs

        obs.metrics.set_default(None)
        with open(args.metrics, "w") as f:
            f.write(reg.render())
        print(f"metrics exposition -> {args.metrics}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
