"""The held experts' grouped products as a share of the chip's peak
(%): their model FLOPs, 6·d·f per slot of the sigma pass (three
matmuls forward) and 18·d·f per slot of the gradient pass (forward and
backward), from the mean ``sigma_slots`` and ``grad_slots`` of the
program's ``moe.load`` span over the traced window rounds, over the
device time of the products (``experts_ms``, whose op names it reads),
over the peak. It counts the model's work, not the implementation's.
``None`` where either part is missing."""
import importlib.util
import os

import numpy as np


def _experts_ms():
    spec = importlib.util.spec_from_file_location(
        "bench_metrics_experts_ms",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "experts_ms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    nodes = ctx.span_nodes("moe.load")
    ms = _experts_ms().read(ctx)
    if not nodes or not ms or not ctx.peak_flops:
        return None
    sigma = np.mean([np.sum(n.attrs["sigma_slots"]) for n in nodes])
    grad = np.mean([np.sum(n.attrs["grad_slots"]) for n in nodes])
    df = ctx.cfg["hidden_size"] * ctx.cfg["moe_intermediate_size"]
    flops = 6 * df * sigma + 18 * df * grad
    return 100.0 * flops / (ms * 1e-3) / ctx.peak_flops
