"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace.py OUT.xplane.pb

Runs on a TPU: three steps, each a step annotation around two small
jitted programs with a host pause between them, so the trace has
device operations, idle gaps, and gaps inside and between steps.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py: needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    g = jax.jit(lambda x: jnp.sum(x * x, axis=0))
    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready((f(x), g(x)))
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("feel_round", step_num=i):
            y = jax.block_until_ready(f(x))
            time.sleep(0.002)
            jax.block_until_ready(g(y))
        time.sleep(0.001)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0],
                out)
    shutil.rmtree(log_dir)


if __name__ == "__main__":
    main(sys.argv[1])
