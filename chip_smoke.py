"""Smoke run of the FEEL round on one TPU chip at the paper's §VI-A size.

    python chip_smoke.py

Runs in one process and starts no other.  Every phase raises on a
failure, so the script exits non-zero at the first one; no phase falls
back to the CPU or carries on past an error.

1. device: the default backend must be a TPU.
2. paper round: ``FEELTrainer`` at full width (28x28 images, the paper's
   CNN, K=10, N=5, Q=2, |D̂_k|=200 of 600 samples per device, scheme
   ``proposed``, faithful selection, closed-form power); one compile
   round and five timed rounds, each clean (no solver fallback),
   feasible, with finite params and cost.
3. CPU reference: the same trainer on this process's CPU backend.
   Round-0 sigma (at the TPU's default matmul precision) and g_hat (at
   HIGHEST precision on the TPU) agree with the CPU's, and
   ``joint.proposed_scheme`` makes the same decision on both backends
   from identical host inputs.
4. compiled sigma kernel: with ``sigma_method="last_layer_kernel"`` the
   sigma program holds a Mosaic ``tpu_custom_call`` and its scores equal
   the jnp ``last_layer`` path.
5. decision stack: the K=256 instance of ``benchmarks/scale.py`` (batched
   matching, closed-form power, selection) and one CCP power solve at
   K=10/N=5 agree between TPU and CPU.

The last line printed is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Timings printed here come from a smoke run, not from a benchmark.
"""
from __future__ import annotations

import dataclasses
import json
import os
import statistics
import sys
import time
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.scale import CONFIG as SCALE_CONFIG  # noqa: E402
from benchmarks.scale import _make_instance  # noqa: E402
from repro.core import default_system, sample_round  # noqa: E402
from repro.core import joint, matching, power, selection  # noqa: E402
from repro.core.types import RoundState  # noqa: E402
from repro.fed import FEELTrainer, paper_setup, server  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Paper sizes (§VI-A).
SIDE, D_HAT, ROUNDS = 28, 200, 6
SCALE_K = 256

# On a TPU an f32 matmul or convolution at default precision rounds both
# operands to bfloat16 (8 significant bits, unit roundoff u = 2^-8) and
# accumulates in f32, so every product carries up to 2u relative error.
# sigma chains four such layers of the CNN (two convs, two dense) before
# the head: about 8u = 2^-5.  atol = rtol * max|ref| covers entries
# that cancel.
SIGMA_RTOL = 2.0 ** -5
# g_hat sums one term per selected sample, so a ReLU or max-pool unit
# that bf16 rounding flips moves an entry by a whole sample's term, not
# by a fraction of it.  The TPU side of g_hat therefore runs at HIGHEST
# matmul precision (f32-accurate), which leaves f32 summation order and
# rare near-tie flips: 2^-8 of the leaf's largest entry.
GHAT_RTOL = 2.0 ** -8
# The closed-form powers gamma*N0*(1+gamma)^r/h evaluate pow twice
# (gamma = 2^(L/BT) - 1).  The TPU's f32 exp/log differ from the CPU's by
# a few 1e-6 relative; the subtraction in gamma (~0.47) amplifies that
# about 3x and the SIC rank r <= Q-1 multiplies the log error: 1e-4
# covers Q=8 (K=256).  Matmul precision plays no part here.
POW_RTOL = 1e-4
# CCP stops when the upload cost moves by < 1e-4 relative between
# iterations; two backends may stop one iterate apart.
CCP_RTOL = 1e-3
# Kernel vs jnp sigma: the same f32 math; only summation order differs.
KERNEL_RTOL = 1e-4


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def close(name: str, got, want, rtol: float, norm_atol: bool = False):
    """allclose with the max deviations printed; raises on mismatch."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    atol = rtol * float(np.max(np.abs(want))) if norm_atol else 0.0
    diff = np.abs(got - want)
    rel = diff / np.maximum(np.abs(want), np.finfo(np.float32).tiny)
    print(f"  {name}: max|diff|={diff.max():.3e} max rel={rel.max():.3e} "
          f"(rtol={rtol:.1e}, atol={atol:.1e})")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=name)


def cpu():
    """Context in which new arrays and computations go to the CPU."""
    return jax.default_device(jax.devices("cpu")[0])


def paper_trainer(side: int = SIDE, d_hat: int = D_HAT, **cfg_changes):
    sys_, data, model, params, cfg = paper_setup(side=side, d_hat=d_hat)
    cfg = dataclasses.replace(cfg, **cfg_changes)
    return FEELTrainer(sys_, data, model, params, cfg)


def phase_device():
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (default backend is "
                         f"{dev.platform!r}); refusing to fall back")
    print(f"device: kind={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} libtpu={metadata.version('libtpu')}")
    return dev, len(devices)


def phase_paper_round(side: int = SIDE, d_hat: int = D_HAT,
                      rounds: int = ROUNDS):
    tr = paper_trainer(side, d_hat)
    walls = []
    for i in range(rounds):
        t0 = time.perf_counter()
        m = tr.run_round(i, eval_now=(i == 0))
        jax.block_until_ready(tr.params)
        walls.append(time.perf_counter() - t0)
        finite = all(bool(jnp.all(jnp.isfinite(x)))
                     for x in jax.tree.leaves(tr.params))
        print(f"  round {i}: wall_s={walls[-1]:.6f} "
              f"fallbacks={m.fallbacks} feasible={m.feasible} "
              f"n_selected={m.n_selected} n_uploaded={m.n_uploaded} "
              f"cum_net_cost={m.cum_net_cost:+.6f} acc={m.test_acc}")
        require(m.fallbacks == (), f"round {i} fell back: {m.fallbacks}")
        require(m.feasible, f"round {i} decision infeasible")
        require(finite, f"round {i} left non-finite params")
        require(bool(np.isfinite(m.cum_net_cost)),
                f"round {i} cum_net_cost {m.cum_net_cost}")
    steady = walls[1:]
    print(f"paper round: compile_round_s={walls[0]:.6f} "
          f"steady_round_s median={statistics.median(steady):.6f} "
          f"min={min(steady):.6f} max={max(steady):.6f} (smoke timing)")


def phase_cpu_reference(side: int = SIDE, d_hat: int = D_HAT):
    tr = paper_trainer(side, d_hat)
    with cpu():
        tr_c = paper_trainer(side, d_hat)
    # both trainers draw round 0's batch from identically seeded host RNGs
    images, labels, _ = tr._gather_round_batches()
    with cpu():
        images_c, labels_c, _ = tr_c._gather_round_batches()
        sigma_c = np.asarray(tr_c._sigma_all(tr_c.params, images_c,
                                             labels_c))
        st = sample_round(jax.random.PRNGKey(0), tr_c.sys)
        h, alpha = np.asarray(st.h), np.asarray(st.alpha)
    require(np.array_equal(np.asarray(images), np.asarray(images_c)),
            "round-0 batches differ between backends")
    sigma = np.asarray(tr._sigma_all(tr.params, images, labels))
    close("sigma", sigma, sigma_c, SIGMA_RTOL, norm_atol=True)

    cfg = tr.cfg

    def decide(sys_):
        state = RoundState(h=jnp.asarray(h), alpha=jnp.asarray(alpha),
                           sigma=jnp.asarray(sigma_c),
                           sigma_mask=jnp.ones(sigma_c.shape, jnp.float32))
        return joint.proposed_scheme(
            sys_, state, selection_method=cfg.selection_method,
            power_evaluator=cfg.power_evaluator, gp_steps=cfg.gp_steps,
            gp_step0=cfg.gp_step0, matching_mode=cfg.matching_mode,
            selection_chunk=cfg.selection_chunk)

    dec = decide(tr.sys)
    with cpu():
        dec_c = decide(tr_c.sys)
    for d in (dec, dec_c):
        require(d.fallbacks == () and d.feasible,
                f"decision fell back or is infeasible: {d.fallbacks}")
    require(np.array_equal(dec.rho, dec_c.rho), "rho differs")
    close("p", dec.p, dec_c.p, POW_RTOL)
    n_diff = int(np.sum(dec.delta != dec_c.delta))
    print(f"  delta: {n_diff} of {dec.delta.size} entries differ")
    require(n_diff == 0, "delta differs")

    # g_hat on the same decision on both backends
    delta = dec_c.delta
    uploaded = alpha * (dec_c.rho.sum(axis=1) > 0)

    def g_hat(t, ims, lbs):
        grads = t._local_grads(t.params, ims, lbs, jnp.asarray(delta))
        return server.aggregate_gradients(t.sys, grads,
                                          jnp.asarray(uploaded, jnp.float32))

    with jax.default_matmul_precision("highest"):
        g = g_hat(tr, images, labels)
    g = jax.tree_util.tree_flatten_with_path(g)[0]
    with cpu():
        g_c = jax.tree.leaves(g_hat(tr_c, images_c, labels_c))
    for (path, leaf), want in zip(g, g_c):
        close(f"g_hat{jax.tree_util.keystr(path)}", leaf, want, GHAT_RTOL,
              norm_atol=True)
    print("cpu reference: sigma, decisions, g_hat agree")
    return tr


def phase_sigma_kernel(tr_ref, side: int = SIDE, d_hat: int = D_HAT):
    tr = paper_trainer(side, d_hat, sigma_method="last_layer_kernel")
    m = tr.run_round(0)
    jax.block_until_ready(tr.params)
    require(m.fallbacks == () and m.feasible,
            f"kernel round fell back or is infeasible: {m.fallbacks}")
    images, labels, _ = tr._gather_round_batches()
    hlo = tr._sigma_all.lower(tr.params, images, labels).compile().as_text()
    require("tpu_custom_call" in hlo,
            "sigma program has no tpu_custom_call: the kernel was "
            "interpreted, not compiled")
    close("kernel sigma vs last_layer",
          tr._sigma_all(tr.params, images, labels),
          tr_ref._sigma_all(tr.params, images, labels), KERNEL_RTOL)
    print("sigma kernel: compiled (tpu_custom_call), agrees with jnp path")


def _scale_stack(K: int):
    sys_, h, alpha, sigma = _make_instance(
        K, 0, np.random.default_rng(SCALE_CONFIG["seed"]))
    match = matching.swap_matching(sys_, h, alpha, mode="batched")
    # swap_matching ends with the closed-form power solve: match.p
    delta = selection.solve_selection(sys_, sigma, jnp.ones_like(sigma),
                                      steps=SCALE_CONFIG["gp_steps"])
    return match, np.asarray(delta)


def _ccp():
    sys_ = default_system(K=10, N=5, Q=2)
    h = np.asarray(sample_round(jax.random.PRNGKey(1), sys_).h, np.float64)
    alpha = np.ones(10)
    rho = matching.swap_matching(sys_, h, alpha).rho
    p, _, ok = power.allocate_power(sys_, jnp.asarray(rho),
                                    jnp.asarray(h, jnp.float32),
                                    jnp.asarray(alpha, jnp.float32),
                                    method="ccp")
    return np.asarray(p), ok


def phase_decision_stack(K: int = SCALE_K):
    t0 = time.perf_counter()
    match, delta = _scale_stack(K)
    t_tpu = time.perf_counter() - t0
    with cpu():
        match_c, delta_c = _scale_stack(K)
    require(match.feasible and match_c.feasible, "scale matching infeasible")
    require(np.array_equal(match.assign, match_c.assign),
            f"K={K} matching assignments differ")
    close(f"K={K} closed-form p", match.p, match_c.p, POW_RTOL)
    n_diff = int(np.sum(delta != delta_c))
    print(f"  K={K} selection: {n_diff} of {delta.size} entries differ")
    require(n_diff == 0, f"K={K} selection differs")
    p, ok = _ccp()
    with cpu():
        p_c, ok_c = _ccp()
    require(ok and ok_c, "CCP infeasible")
    close("K=10 CCP p", p, p_c, CCP_RTOL)
    print(f"decision stack: K={K} ({match.swaps} swaps, tpu side "
          f"{t_tpu:.3f}s) and CCP agree")


def main() -> None:
    enable_compile_cache()
    dev, count = phase_device()
    phase_paper_round()
    tr_ref = phase_cpu_reference()
    phase_sigma_kernel(tr_ref)
    phase_decision_stack()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))


if __name__ == "__main__":
    main()
