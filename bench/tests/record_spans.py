"""Record the device trace that ``test_trace_spans.py`` reads.

    python bench/tests/record_spans.py OUT.xplane.pb

Runs on a TPU: three rounds under the profiler, each a ``round`` span of
a recording ``repro.obs.Telemetry`` whose spans make known numbers of
device program launches and transfers to the host (``KNOWN``, per round).
It prints, per span, the host events that start inside it.
"""
import collections
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

from repro import obs  # noqa: E402

ROUNDS = 3
#: per round and row: (launches, transfers to the host)
KNOWN = {"a": (3, 0), "b": (2, 2), "c": (2, 1), "telemetry": (1, 1),
         "round": (1, 0)}


def one_round(tele, f, g, x):
    with tele.span("round"):
        with tele.stage("a"):             # three launches and a wait
            y = tele.block(f(f(f(x))))
            time.sleep(0.002)
        with tele.stage("b"):             # two launches, two transfers
            float(g(y))
            np.asarray(f(y))
        with tele.span("c"):
            with tele.span("c.inner"):    # not a row: counts toward c
                float(jnp.sum(y + 1.0))   # two eager launches, a transfer
        with tele.span("telemetry"):      # left out of the totals
            int(g(y))                     # a launch and a transfer
        f(x)                              # the round's own launch


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_spans.py: needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    g = jax.jit(lambda x: jnp.sum(x * x))
    x = jnp.ones((256, 256), jnp.float32)
    warm = obs.Telemetry()
    for _ in range(2):
        one_round(warm, f, g, x)
    log_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    tele = obs.Telemetry()
    for _ in range(ROUNDS):
        one_round(tele, f, g, x)
        time.sleep(0.001)
    jax.profiler.stop_trace()
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out)
    shutil.rmtree(log_dir)

    from jax.profiler import ProfileData

    host = [(e.start_ns, e.end_ns, e.name)
            for p in ProfileData.from_file(out).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    names = {"round", "a", "b", "c", "c.inner", "telemetry"}
    for s, e, n in sorted(h for h in host if h[2] in names):
        inside = collections.Counter(m for t, _, m in host
                                     if s <= t < e and m not in names)
        print(n, dict(inside))


if __name__ == "__main__":
    main(sys.argv[1])
