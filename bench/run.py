"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload paper_k10 --seed 7 --seconds 30 --trace 0

One process, one cell, one run. The cell (``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``: the model's widths and
the deployment) and a traffic mix (``bench/traffic/<mix>.json``: the
scheme and the devices' availability). The configuration's ``"model"``
names its model module, ``bench/models/<model>.py`` (``cnn`` where the
key is absent), which makes the data, the weights, the program's model
object, the FLOPs and the reference's model part. From ``--seed`` the
run makes the data and the weights, builds one ``FEELTrainer`` and
drives it through its first rounds (set-up: compilation and warm-up),
then measures a closed loop of ``run_round`` calls, each ended by
``block_until_ready(trainer.params)``, for ``--seconds``. With
``--trace 1`` the program's telemetry records stage spans in the window
(each stage then ends in a ``block_until_ready`` of its own) and a few
more rounds run under the profiler for the device trace.

JAX's persistent compile cache is kept in ``<checkout>/.jax_cache``, so
the first run in a checkout compiles and every later one finds its
programs there.

After the window the first rounds are checked against the plain
reference (``reference.py``, ``correct.py``). Each metric is computed
by its reader, ``bench/metrics/<metric>.py``, from a ``Context``: the
window's walls, each traced round's span tree, and the profile's device
time by op and by program. The last line on stdout is the result as
JSON; the numbers compared, beside their limits, are the last lines on
stderr. Without a TPU the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import correct  # noqa: E402
import inputs  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

#: rounds driven in set-up; they warm every program up and are checked.
SETUP_ROUNDS = 3
#: the profiled sub-window of a traced run: at least this long and
#: this many rounds.
PROFILE_S = 1.0
PROFILE_ROUNDS = 2
STEP_NAME = "feel_round"
#: the model module of a configuration that names none
DEFAULT_MODEL = "cnn"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    metrics: List[dict]      # end_to_end with --trace 0, else per_layer
    model: Any               # the configuration's model module


def load_cell(workload: str, trace: bool) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    config = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in spec[kind]
               if workload in m.get("workloads", [workload])]
    model = model_module(cfg.get("model", DEFAULT_MODEL))
    return Cell(workload, w["chips"], cfg, traffic, metrics, model)


def _load(folder: str, name: str):
    """The module ``bench/<folder>/<name>.py``, loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(BENCH, folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _load("metrics", name).read


def model_module(name: str):
    """``bench/models/<name>.py``; an unknown name exits with the list
    of known model modules."""
    folder = os.path.join(BENCH, "models")
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(folder)
                       if f.endswith(".py"))
        raise SystemExit(f"unknown model {name!r}: no bench/models/"
                         f"{name}.py; known: {known}")
    return _load("models", name)


@dataclasses.dataclass
class Context:
    """What a metric's reader reads."""

    setup_s: float
    walls: List[float]                 # each window round's seconds
    window_s: float
    cfg: dict = dataclasses.field(default_factory=dict)
    model: Any = None                  # the configuration's model module
    #: per traced window round: {"round": dur_s, <stage>: dur_s, ...}
    spans: List[Dict[str, float]] = dataclasses.field(default_factory=list)
    #: per traced window round, its ``round`` span tree with attributes
    trees: List[Any] = dataclasses.field(default_factory=list)
    profile: Optional[dict] = None     # trace_reduce.reduce() output
    profile_flops: float = 0.0         # model FLOPs of the profiled rounds
    profile_selected: List[int] = dataclasses.field(default_factory=list)
    peak_flops: float = 0.0
    peak_bytes_per_s: float = 0.0

    def stage_ms(self, stage: str) -> Optional[float]:
        """Mean per round of the ``round/<stage>`` span, inclusive."""
        if not self.spans or not any(stage in s for s in self.spans):
            return None
        return 1e3 * sum(s.get(stage, 0.0) for s in self.spans) \
            / len(self.spans)

    def span_nodes(self, name: str) -> list:
        """Every span named ``name``, at any depth, of the traced rounds
        (a ``repro.obs.spans.SpanNode``: ``dur_s``, ``attrs``, ...)."""
        return [n for t in self.trees for n in t.walk() if n.name == name]

    def span_ms(self, name: str) -> Optional[float]:
        """Mean per traced round of the spans named ``name`` at any
        depth, inclusive, summed within a round (one inside another
        counted once); ``None`` where no round has one."""
        def outermost(node):
            if node.name == name:
                return node.dur_s
            return sum(outermost(c) for c in node.children)

        if not self.span_nodes(name):
            return None
        return 1e3 * sum(map(outermost, self.trees)) / len(self.trees)

    def program_ms(self, name: str) -> Optional[float]:
        """Device ms per profiled round of the XLA programs named
        ``name`` (``jit_<function>``); ``None`` where none ran."""
        return self._per_round("programs_s", name)

    def op_ms(self, name: str) -> Optional[float]:
        """Device ms per profiled round of the operation ``name`` as the
        trace names it (``%fusion.6``); ``None`` where it did not run."""
        return self._per_round("ops_s", name)

    def _per_round(self, table: str, name: str) -> Optional[float]:
        if self.profile is None or name not in self.profile[table]:
            return None
        return 1e3 * self.profile[table][name] / self.profile["steps"]


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:9.3f} s] {msg}", file=sys.stderr,
          flush=True)


def require_tpu(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench/run.py: needs {chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform!r} device(s); "
              f"no fallback", file=sys.stderr)
        sys.exit(3)
    return devices


def enable_compile_cache() -> str:
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    def __init__(self):
        self.active = False
        self.counts = {e: 0 for e in COMPILE_EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.counts:
            self.counts[event] += 1


def build(cell: Cell, sub: dict, tele):
    """``(samples, params0, trainer)`` of one run."""
    from repro.core.types import SystemParams
    from repro.fed import FEELConfig, FEELTrainer

    cfg, traffic, model = cell.cfg, cell.traffic, cell.model
    K = cfg["K"]
    data = model.samples(cfg, sub["data"])
    fed = model.dataset(cfg, data)
    k1 = np.arange(1, K + 1)
    odd = k1 % 2 == 1

    def per_device(a, b):
        return jnp.asarray(np.where(odd, a, b), jnp.float32)

    sys_ = SystemParams(
        K=K, N=cfg["N"], Q=cfg["Q"], B=jnp.asarray(cfg["B_hz"]),
        T=jnp.asarray(cfg["T_s"]), L=jnp.asarray(cfg["L_bits"]),
        N0=jnp.asarray(cfg["N0_w"]), p_max=jnp.full((K,), cfg["p_max_w"]),
        q=per_device(cfg["q_odd"], cfg["q_even"]),
        c=per_device(cfg["c_odd"], cfg["c_even"]),
        f=jnp.asarray(cfg["f_hz_step"] * (1 + (k1 - 1) % 10), jnp.float32),
        F=jnp.full((K,), cfg["F_cycles"]), kappa=jnp.asarray(cfg["kappa"]),
        eps=per_device(traffic["eps_odd"], traffic["eps_even"]),
        D_hat=jnp.full((K,), float(cfg["d_hat"])),
        lam=jnp.asarray(cfg["lam"]))
    params0 = model.init_params(cfg, sub["weights"])
    fcfg = FEELConfig(scheme=traffic["scheme"], d_hat=cfg["d_hat"],
                      optimizer=cfg["optimizer"], lr=cfg["lr"],
                      gp_steps=cfg["gp_steps"], gp_step0=cfg["gp_step0"],
                      seed=sub["rounds"])
    trainer = FEELTrainer(sys_, fed, model.program(cfg), params0, fcfg,
                          telemetry=tele)
    return data, params0, trainer


def first_rounds(trainer, params0) -> correct.Observed:
    """Drive the trainer's first rounds through ``run_round``; return
    what they produced, each round's decision as ``RoundMetrics``
    reports the one it applied."""
    states, metrics = [], []
    for i in range(SETUP_ROUNDS):
        metrics.append(trainer.run_round(i, eval_now=False))
        jax.block_until_ready(trainer.params)
        states.append(trainer.opt_state)
    seen = [m.decision for m in metrics]
    if any(d is None for d in seen):
        raise RuntimeError(
            "a round's RoundMetrics carries no decision: the program's "
            "decisions cannot be read, so they cannot be checked")
    first = next((s for s in states if int(s.count) == 1), None)
    return correct.Observed(
        rho=[d.rho for d in seen], p=[d.p for d in seen],
        delta=[d.delta for d in seen],
        n_uploaded=[m.n_uploaded for m in metrics],
        skipped=[m.skipped_update for m in metrics],
        first_grad=None if first is None else jax.tree.map(
            lambda a: np.asarray(a) / (1 - reference.ADAM_B1), first.mu),
        params0=jax.tree.map(np.asarray, params0),
        params=jax.tree.map(np.asarray, trainer.params))


def round_failed(m) -> bool:
    return bool(m.fallbacks != () or not m.feasible or m.skipped_update)


def _round_trees(tele, rounds: range) -> Dict[int, Any]:
    """round -> its ``round`` span tree."""
    from repro.obs.spans import build_tree

    roots, _ = build_tree(tele.events)
    return {r.round: r for r in roots
            if r.name == "round" and r.round in rounds}


def _stage_rows(trees: Dict[int, Any]) -> Dict[int, tuple]:
    """round -> (round_t0_s, round dur_s, [(stage, t0_s, dur_s), ...])."""
    return {i: (r.t0_s, r.dur_s, [(c.name, c.t0_s, c.dur_s)
                                  for c in r.children if c.kind == "stage"])
            for i, r in trees.items()}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True,
        cfg_changes: Optional[dict] = None,
        limits: Optional[Dict[str, float]] = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``require_chip``, ``cfg_changes`` and ``limits`` exist for tests
    (a small configuration on the CPU); the command never sets them.
    """
    from repro import obs

    cell = load_cell(workload, trace)
    if cfg_changes:
        cell.cfg = {**cell.cfg, **cfg_changes}
    devices = require_tpu(cell.chips) if require_chip else jax.devices()
    dev = devices[0]
    cache_dir = enable_compile_cache()
    print(f"cell {workload}: seed={seed} seconds={seconds} trace={int(trace)}"
          f" device={dev.device_kind} x{len(devices)} cache={cache_dir}",
          file=sys.stderr)
    cfg, model = cell.cfg, cell.model
    sub = inputs.seeds(seed)
    tele = obs.Telemetry() if trace else obs.NULL
    data, params0, trainer = build(cell, sub, tele)
    counter = CompileCounter()

    # ---- set-up: the first rounds compile, warm up, and are checked --
    observed = first_rounds(trainer, params0)
    setup_s = time.perf_counter() - T_START
    log(f"set-up done: {setup_s:.3f} s")

    # ---- the measured window: a closed loop of rounds ----------------
    walls, failed, error = [], 0, None
    i = SETUP_ROUNDS
    counter.active = True
    t_open = t_end = time.perf_counter()
    while t_end - t_open < seconds:
        t0 = time.perf_counter()
        try:
            m = trainer.run_round(i, eval_now=False)
            jax.block_until_ready(trainer.params)
        except Exception as e:  # a round that raises fails the run
            error = f"round {i} raised {type(e).__name__}: {e}"
            failed += 1
            break
        t_end = time.perf_counter()
        walls.append(t_end - t0)
        failed += round_failed(m)
        i += 1
    counter.active = False
    window_rounds = range(SETUP_ROUNDS, i)
    log(f"window done: {len(walls)} rounds")
    print("compiles in window: " + " ".join(
        f"{k.rsplit('/', 1)[-1]}={v}" for k, v in counter.counts.items()),
        file=sys.stderr)

    ctx = Context(setup_s=setup_s, walls=walls,
                  window_s=t_end - t_open, cfg=cfg, model=model)
    attempted = len(walls) + (error is not None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    breakdown = None
    if trace and error is None:
        trees = _round_trees(tele, window_rounds)
        ctx.trees = list(trees.values())
        ctx.spans = [{"round": r[1], **_sum_stages(r[2])}
                     for r in _stage_rows(trees).values()]
        # without a chip there is no device plane to profile
        prof = None
        if require_chip:
            prof, prof_metrics, failed_p, error = _profile(trainer, i, tele)
            attempted += len(prof_metrics) + (error is not None)
            failed += failed_p
        if prof is not None:
            ctx.profile = prof
            ctx.profile_selected = [m.n_selected for m in prof_metrics]
            ctx.profile_flops = float(sum(
                model.round_flops(cfg, n) for n in ctx.profile_selected))
            peak = peaks.peak(dev.device_kind)
            ctx.peak_flops = peak["flops"]
            ctx.peak_bytes_per_s = peak["hbm_bytes_per_s"]
            device["busy_s"] = prof["busy_s"]
            device["window_s"] = prof["window_s"]
            breakdown = {"device_ops": prof["device_ops"],
                         "idle_gaps": prof["idle_gaps"]}
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    values = {}
    for m in cell.metrics:
        v = reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- correctness: the first rounds against the reference --------
    del trainer, tele
    gc.collect()
    log("reference: start")
    ref = reference.Reference(cfg, cell.traffic, sub, model).run(
        data, params0, rounds=SETUP_ROUNDS, selections=observed.delta)
    log("reference: done")
    numbers = correct.compare(observed, ref)
    rounds = correct.per_round(observed, ref)
    ok, checks = correct.judge(numbers, limits or correct.limits(workload))
    result = {"correct": bool(ok and error is None),
              "attempted": attempted, "failed": failed,
              "metrics": values, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["rounds"] = rounds
    result["checks"] = checks
    if error is not None:
        print(error, file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def _sum_stages(stages) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, dur in stages:
        out[name] = out.get(name, 0.0) + dur
    return out


def _profile(trainer, first_round: int, tele):
    """Rounds under the profiler, each in a step annotation; returns
    ``(reduced trace, their RoundMetrics, failures, error)``."""
    log_dir = tempfile.mkdtemp(prefix="feel_profile_")
    ms, failed, error = [], 0, None
    i = first_round
    try:
        # no Python tracer: it records every Python call of the round
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        t0 = time.perf_counter()
        try:
            while (len(ms) < PROFILE_ROUNDS
                   or time.perf_counter() - t0 < PROFILE_S):
                with jax.profiler.StepTraceAnnotation(STEP_NAME, step_num=i):
                    m = trainer.run_round(i, eval_now=False)
                    jax.block_until_ready(trainer.params)
                ms.append(m)
                failed += round_failed(m)
                i += 1
        except Exception as e:
            error = f"round {i} raised {type(e).__name__}: {e}"
            failed += 1
        finally:
            jax.profiler.stop_trace()
        log(f"profile: {len(ms)} rounds traced")
        if error is not None:
            return None, ms, failed, error
        devices, programs, steps = trace_reduce.read_xplane(
            trace_reduce.find_xplane(log_dir), STEP_NAME)
        rows = _stage_rows(_round_trees(tele, range(first_round, i)))
        spans = {k: (v[0], v[2]) for k, v in rows.items()}
        out = trace_reduce.reduce(devices, steps, spans, programs=programs)
        log(f"profile: reduced {sum(map(len, devices.values()))} device "
            f"events")
        return out, ms, failed, None
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
