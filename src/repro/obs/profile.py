"""Kernel roofline profiling: FLOPs/bytes per jitted function.

``cost_of`` lowers + compiles a jitted function ahead-of-time and reads
XLA's ``cost_analysis()`` — HLO FLOPs and bytes accessed.  ``profile_jitted``
wraps that into a ``ProfileEvent`` (schema v2) recorded once per
(function, input shapes) compilation, stamped with the backend's
estimated peak FLOP/s so achieved-vs-peak utilization can be computed
later, on any machine, from the trace alone:

    utilization(stage) = flops / (stage seconds per call) / peak_flops

``repro.obs.summary`` joins profile events against stage timings to
surface exactly that (``telemetry.roofline.<stage>`` rows), and
``benchmarks/roofline.py --trace`` prints the same table standalone.

Peak FLOP/s is calibrated once per process by timing a dense f32
matmul (override with ``REPRO_PEAK_FLOPS=<float>`` for a known part —
e.g. a TPU v4 chip's 2.75e14 bf16 FLOP/s — or to pin CI numbers).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, Optional, Tuple

from . import events as ev
from . import metrics as metrics_mod
from . import trace as trace_mod

_PEAK_CACHE: Optional[float] = None


def peak_flops() -> float:
    """Estimated peak FLOP/s of the default backend (cached).

    Honors ``REPRO_PEAK_FLOPS``; otherwise times a 1024^3 f32 matmul
    (best of three) — a *practical* peak, which is the right
    denominator for "how much of what this machine can do did we use".
    """
    global _PEAK_CACHE
    if _PEAK_CACHE is not None:
        return _PEAK_CACHE
    env = os.environ.get("REPRO_PEAK_FLOPS")
    if env:
        _PEAK_CACHE = float(env)
        return _PEAK_CACHE
    import jax
    import jax.numpy as jnp

    n = 1024
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    mm(a).block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        mm(a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    _PEAK_CACHE = 2.0 * n ** 3 / max(best, 1e-9)
    return _PEAK_CACHE


def cost_of(fn, *args) -> Dict[str, float]:
    """Lower + compile ``fn`` (a ``jax.jit`` callable) on ``args`` and
    return ``{"flops", "bytes_accessed", "compile_s"}`` from XLA's cost
    analysis."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    cost = compiled.cost_analysis() or {}
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "compile_s": compile_s}


@dataclasses.dataclass
class KernelProfile:
    """One profiled compilation (the in-memory face of ``ProfileEvent``)."""

    name: str
    stage: Optional[str]
    flops: float
    bytes_accessed: float
    peak_flops: float
    compile_s: float

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.bytes_accessed, 1.0)

    def utilization(self, wall_s_per_call: float) -> float:
        """Achieved / peak FLOP/s for one execution of this kernel."""
        if wall_s_per_call <= 0.0 or self.peak_flops <= 0.0:
            return 0.0
        return self.flops / wall_s_per_call / self.peak_flops


def profile_jitted(fn, args: Tuple[Any, ...], name: str,
                   stage: Optional[str] = None, telemetry=None,
                   registry=None,
                   round: Optional[int] = None) -> KernelProfile:
    """Profile one jitted function, emit the ``ProfileEvent`` and the
    ``feel_kernel_*`` gauges, and return the ``KernelProfile``."""
    cost = cost_of(fn, *args)
    prof = KernelProfile(name=name, stage=stage, flops=cost["flops"],
                         bytes_accessed=cost["bytes_accessed"],
                         peak_flops=peak_flops(),
                         compile_s=cost["compile_s"])
    tele = trace_mod.resolve(telemetry)
    tele.emit(ev.ProfileEvent(name=name, stage=stage, flops=prof.flops,
                              bytes_accessed=prof.bytes_accessed,
                              peak_flops=prof.peak_flops,
                              compile_s=prof.compile_s, round=round))
    reg = metrics_mod.resolve(registry)
    if reg.enabled:
        reg.gauge("feel_kernel_flops",
                  "HLO FLOPs per call of each jitted kernel").set(
                      prof.flops, kernel=name)
        reg.gauge("feel_kernel_bytes",
                  "HLO bytes accessed per call of each jitted kernel").set(
                      prof.bytes_accessed, kernel=name)
    return prof
