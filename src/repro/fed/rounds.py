"""The FEEL communication-round loop (paper §II + Algorithm 1).

Each round:
  1. every device samples |D̂_k| local samples and scores them
     (sigma_{k,j} = per-sample gradient-norm^2);
  2. channels h_{k,n} and availability alpha_k are drawn;
  3. the server runs Algorithm 1 (or a baseline scheme) to fix
     (rho*, p*, delta*) and is billed the net cost (eq. 18);
  4. devices compute local gradients on their *selected* samples
     (eq. 4) — FedSGD; with ``local_steps > 1`` the FedAvg variant of
     footnote 4 runs multiple local steps and uploads model deltas;
  5. the server aggregates with inverse-propensity weights (eq. 19)
     and applies the optimizer update (eq. 20; Adam in §VI-A).

The programs follow the model and its size.  A model over token
sequences (``sequence = True``, ``models.control_lm``) scores its
sequences device by device (``lax.map``), so the (K·|D̂|, S, V) logits
never exist at once.  Where K gradient trees would take more than
``GRAD_TREES_SHARE`` of the device's memory, the local gradients are a
``lax.scan`` over devices that adds each device's eq. (4) gradient,
weighted by eq. (19), into one tree; the server step applies the
optimizer to that sum.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Callable, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import checkpoint as ckpt_mod
from .. import obs, optim
from ..obs import metrics as metrics_mod
from ..core import cost as cost_mod
from ..core import joint as joint_mod
from ..core.types import RoundState, SystemParams
from ..data.federated import FederatedDataset
from ..models import moe as moe_mod
from . import client as client_mod
from . import faults as faults_mod
from . import server as server_mod

Array = jax.Array

#: checkpoint file prefix inside a checkpoint directory.
CKPT_NAME = "feel_ckpt"
#: largest share of the device's memory that K per-device gradient
#: trees may take; above it the gradients are summed device by device
GRAD_TREES_SHARE = 0.25
#: the device memory assumed where the backend reports none (the CPU):
#: one TPU v5e chip
DEFAULT_DEVICE_BYTES = 16 * 2 ** 30


def device_bytes() -> int:
    """Memory of the default device, as its backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", DEFAULT_DEVICE_BYTES))


def tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


@dataclasses.dataclass
class FEELConfig:
    scheme: str = "proposed"          # proposed | baseline1..baseline4
    selection_method: str = "faithful"  # faithful (Alg 4+5) | exact
    # last_layer | last_layer_kernel (one fused all-device pass through
    # kernels/gradnorm) | full
    sigma_method: str = "last_layer"
    power_evaluator: str = "closed_form"  # closed_form | ccp
    # swap-matching sweep: auto (batched at >= AUTO_BATCH_MIN available
    # devices) | scalar | batched — see docs/solvers.md
    matching_mode: str = "auto"
    # 0 = full-matrix Alg. 4; >0 = lax.map over device blocks that size
    selection_chunk: int = 0
    optimizer: str = "adam"
    lr: float = 1e-3
    d_hat: int = 200
    local_steps: int = 1              # >1 => FedAvg variant
    gp_steps: int = 400
    gp_step0: float = 0.3
    warmup_rounds: int = 0    # select ALL samples first (beyond-paper fix:
                              # sigma is uninformative before the model fits)
    eval_every: int = 10
    seed: int = 0


@dataclasses.dataclass
class ResilienceConfig:
    """Knobs of the fault-tolerance layer (docs/robustness.md).

    Passing one to ``FEELTrainer`` (or passing a ``FaultPlan``) turns
    the resilience policies on; with the defaults and no materialized
    fault every round stays bit-for-bit identical to a plain run.
    """

    #: upload deadline in seconds; None derives 1.5 x the slowest
    #: clean completion max_k(tau_k) + T (eqs. 8 + 16 latency model).
    deadline_s: Optional[float] = None
    #: bounded retries for a straggling upload before it is dropped.
    max_retries: int = 2
    #: exponential backoff: retry t waits until deadline * base**t.
    backoff_base: float = 2.0
    #: mid-round dropout handling: "reweight" renormalizes the IPW
    #: aggregation over survivors; "resolve" additionally re-solves the
    #: RB assignment for the survivor set (cost accounting follows).
    dropout_policy: str = "reweight"
    #: consecutive non-finite uploads before a device is quarantined.
    quarantine_threshold: int = 2
    #: rounds a quarantined device sits out; each clean upload
    #: afterwards decays one strike (skip-with-decay).
    quarantine_rounds: int = 3
    #: checkpoint every N rounds (0 = never) into checkpoint_dir.
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None


@dataclasses.dataclass
class RoundMetrics:
    round: int
    net_cost: float
    cum_net_cost: float
    delta_obj: float
    n_selected: int
    n_uploaded: int
    frac_mislabeled_selected: float
    test_acc: Optional[float] = None
    n_dropped: int = 0          # scheduled uploads lost this round
    n_quarantined: int = 0      # devices sitting out this round
    n_retries: int = 0          # straggler retry attempts this round
    skipped_update: bool = False  # no usable upload -> no optimizer step
    fallbacks: tuple = ()       # solver degradations (RoundDecision)
    feasible: bool = True       # RoundDecision.feasible
    #: the decision the round applied (after a survivor re-solve, the
    #: re-solved one)
    decision: Optional[joint_mod.RoundDecision] = None


class FEELTrainer:
    """Drives FEEL rounds for an image-classification model."""

    def __init__(self, sys: SystemParams, data: FederatedDataset,
                 model, params, cfg: FEELConfig,
                 telemetry: Optional[obs.NullTelemetry] = None,
                 monitor: Optional["obs.ConvergenceMonitor"] = None,
                 faults: Optional[Union["faults_mod.FaultPlan",
                                        "faults_mod.FaultSpec"]] = None,
                 resilience: Optional[ResilienceConfig] = None):
        """``model`` exposes features(params, x), apply, loss_fn, accuracy.

        ``telemetry``: an ``obs`` sink for the round-level trace; the
        default (``None``) resolves to the process-wide sink, which is
        a no-op unless e.g. ``benchmarks/run.py --trace`` installed one.

        ``monitor``: an ``obs.ConvergenceMonitor`` fed one observation
        per round (training-loss gap proxy, ||g_hat||^2, step size, the
        decision's Delta term, wall/stage timings).  ``None`` (default)
        skips every monitor code path — round outputs stay bit-for-bit
        identical.  Metrics flow to the process-default registry
        (``obs.metrics.set_default``), also a no-op unless installed.

        ``faults``: a ``repro.fed.faults.FaultPlan`` (or its spec)
        injecting post-matching dropout, straggler delays, NaN uploads
        and forced solver failures — deterministic and replayable.

        ``resilience``: a ``ResilienceConfig`` with the policy knobs
        (deadline/retry/backoff, dropout policy, quarantine,
        checkpointing).  Either argument activates the resilience
        layer; ``None``+``None`` (default) keeps every round bit-for-
        bit identical to the pre-fault-tolerance trainer.
        """
        self.sys = sys
        self.data = data
        self.model = model
        self.params = params
        self.cfg = cfg
        self.obs = obs.resolve(telemetry)
        self.monitor = monitor
        if isinstance(faults, faults_mod.FaultSpec):
            faults = faults_mod.FaultPlan(faults)
        self.faults = faults
        self.resilience = resilience
        self._resilient = faults is not None or resilience is not None
        self._res = resilience if resilience is not None \
            else ResilienceConfig()
        self._strikes = np.zeros(sys.K, np.int64)
        self._quarantined_until = np.zeros(sys.K, np.int64)
        # devices whose eq. (19) weight is nonzero when they upload
        self._ipw_live = (np.asarray(sys.eps) > 0) & \
            (np.asarray(sys.D_hat) > 0)
        self._start_round = 0
        self._cum = 0.0
        self._profiled: set = set()
        self.rng = np.random.default_rng(cfg.seed)
        self.key = jax.random.PRNGKey(cfg.seed)
        opt_builder = {"adam": optim.adam, "sgd": optim.sgd,
                       "momentum": optim.momentum,
                       "adafactor": optim.adafactor}[cfg.optimizer]
        self.opt = opt_builder(cfg.lr)
        self._sequence = bool(getattr(model, "sequence", False))
        grad_trees = sys.K * tree_bytes(params)
        #: the local gradients are summed device by device
        self._stream = grad_trees > GRAD_TREES_SHARE * device_bytes()
        if self._stream and (self._resilient or cfg.local_steps > 1):
            raise ValueError(
                f"K={sys.K} gradient trees take {grad_trees / 1e9:.2f} GB, "
                f"more than {GRAD_TREES_SHARE:.0%} of the device's "
                f"{device_bytes() / 1e9:.2f} GB, so the gradients are "
                "summed device by device; the resilience layer and "
                "local_steps > 1 need per-device trees")
        self.opt_state = self.opt.init(params)
        self._build_jitted()

    @property
    def opt_state(self):
        """The optimizer state.  Where the gradients are summed device by
        device the server step updates the state in place (its buffers
        are donated), so a caller gets a host copy to keep."""
        if self._stream:
            return jax.device_get(self._opt_state)
        return self._opt_state

    @opt_state.setter
    def opt_state(self, state):
        self._opt_state = (jax.device_put(state) if self._stream
                           else state)

    # ------------------------------------------------------------------
    def _build_jitted(self):
        # the programs hold no reference to the trainer, so that a
        # trainer let go frees its device arrays without a gc pass
        model, cfg, opt = self.model, self.cfg, self.opt

        if cfg.sigma_method == "last_layer_kernel":
            @jax.jit
            def sigma_all(params, images, labels):
                """(K, D̂) sigma via one fused all-device kernel pass."""
                return client_mod.batched_sigma(params, images, labels,
                                                features_fn=model.features)
        else:
            @jax.jit
            def sigma_all(params, images, labels):
                """(K, D̂) sigma scores."""
                f = functools.partial(client_mod.per_sample_sigma,
                                      features_fn=model.features,
                                      method=cfg.sigma_method,
                                      loss_fn=model.loss_fn)
                return jax.vmap(lambda im, lb: f(params, im, lb))(images,
                                                                  labels)

        @jax.jit
        def local_grads(params, images, labels, delta):
            """pytree with leading K axis (FedSGD local gradients)."""
            return jax.vmap(
                lambda im, lb, dl: client_mod.local_gradient(
                    params, im, lb, dl, model.loss_fn))(images, labels,
                                                        delta)

        @jax.jit
        def local_deltas(params, images, labels, delta, lr):
            """FedAvg: run local_steps SGD steps, return param deltas."""

            def one_device(im, lb, dl):
                def step(p, _):
                    g = client_mod.local_gradient(p, im, lb, dl,
                                                  model.loss_fn)
                    p = jax.tree.map(lambda a, b: a - lr * b, p, g)
                    return p, None

                p_out, _ = jax.lax.scan(step, params, None,
                                        length=cfg.local_steps)
                # pseudo-gradient: (w - w_k') / lr, aggregated like a grad
                return jax.tree.map(lambda a, b: (a - b) / lr, params, p_out)

            return jax.vmap(one_device)(images, labels, delta)

        def server_step(sys, params, opt_state, grads, alpha, renormalize):
            """eq. (19) and the optimizer step (eq. 20) as one program;
            returns ``(params, opt_state, g_hat)``.

            ``renormalize`` (static) is the survivor path: the lost
            uploads are zeroed before the weighted sum (their IPW weight
            is 0, but 0 * NaN would still poison it) and the sum is
            divided by the survivors' realized IPW mass.  The functions
            are looked up through their modules when the step is traced.
            """
            metrics_mod.get_default().counter(
                "feel_server_step_traces_total",
                "traces of the fused server step").inc()
            if renormalize:
                live = alpha > 0

                def scrub(leaf):
                    shape = (sys.K,) + (1,) * (leaf.ndim - 1)
                    return jnp.where(live.reshape(shape), leaf, 0.0)

                grads = jax.tree.map(scrub, grads)
            g_hat = server_mod.aggregate_gradients(sys, grads, alpha,
                                                   renormalize=renormalize)
            updates, opt_state = opt.update(g_hat, opt_state, params)
            return optim.apply_updates(params, updates), opt_state, g_hat

        if self._sequence:
            self._build_sequence_jitted()
        else:
            self._sigma_all = sigma_all
            self._local_grads = local_grads
        if self._stream:
            self._local_grads = self._streamed_local_grads()
        self._local_deltas = local_deltas
        self._server_step = jax.jit(server_step,
                                    static_argnames=("renormalize",))

        def sum_step(params, opt_state, g_hat):
            """The optimizer step (eq. 20) on the eq. (19) sum."""
            updates, opt_state = opt.update(g_hat, opt_state, params)
            return optim.apply_updates(params, updates), opt_state

        self._sum_step = jax.jit(sum_step, donate_argnums=(1, 2))

    def _device_grad(self):
        """One device's eq. (4) gradient: ``(grad, slots)``, the slots
        (layers, D̂, n_held) of a sequence model's held experts, else
        None."""
        model = self.model
        if self._sequence:
            return functools.partial(client_mod.sequence_local_gradient,
                                     model=model)

        def device_grad(params, x, labels, delta):
            return client_mod.local_gradient(params, x, labels, delta,
                                             model.loss_fn), None

        return device_grad

    def _build_sequence_jitted(self):
        """sigma and per-device gradients of a model over token sequences,
        each returning the held experts' slots besides."""
        model, device_grad = self.model, self._device_grad()

        @jax.jit
        def sigma_all(params, x, labels):
            """(K, D̂) sigma and the slots (layers, n_held) of every
            scored sequence, one device at a time."""
            def one(args):
                h, r, slots = model.head_residuals(params, *args)
                return client_mod.sequence_sigma(h, r), jnp.sum(slots, 1)

            sigma, slots = jax.lax.map(one, (x, labels))
            return sigma, jnp.sum(slots, axis=0)

        @jax.jit
        def local_grads(params, x, labels, delta):
            """(per-device gradient trees, slots (K, layers, D̂,
            n_held))."""
            return jax.vmap(lambda *a: device_grad(params, *a))(
                x, labels, delta)

        self._sigma_all = sigma_all
        self._local_grads = local_grads

    def _streamed_local_grads(self):
        """The local gradients where K trees do not fit: ``(eq. 19 sum
        of the eq. 4 gradients, slots (K, ...) or None)``, one device at
        a time.  Every device's gradient is computed, a device of weight
        0 adding nothing and counting no slots, so that a round's device
        work does not follow how many devices upload."""
        K, device_grad = self.sys.K, self._device_grad()

        @jax.jit
        def local_grads(params, sys, alpha, x, labels, delta):
            """The weights are eq. (19)'s as ``server.aggregate_gradients``
            gives them (looked up when traced)."""
            with jax.default_matmul_precision("float32"):
                w = server_mod.aggregate_gradients(
                    sys, jnp.eye(K, dtype=jnp.float32), alpha)

            def step(acc, args):
                wk, xk, lk, dk = args
                g, slots = device_grad(params, xk, lk, dk)
                acc = jax.tree.map(lambda a, b: a + wk.astype(b.dtype) * b,
                                   acc, g)
                return acc, jax.tree.map(
                    lambda s: jnp.where(wk != 0, s, 0), slots)

            acc = jax.tree.map(jnp.zeros_like, params)
            return jax.lax.scan(step, acc, (w, x, labels, delta))

        return local_grads

    # ------------------------------------------------------------------
    def _gather_round_batches(self):
        idx = self.data.sample_subsets(self.rng, self.cfg.d_hat)
        imgs = np.stack([self.data.device_images[k][idx[k]]
                         for k in range(self.sys.K)])
        labels = np.stack([self.data.device_labels[k][idx[k]]
                           for k in range(self.sys.K)])
        true = np.stack([self.data.device_true[k][idx[k]]
                         for k in range(self.sys.K)])
        return jnp.asarray(imgs), jnp.asarray(labels), true


    def run_round(self, i: int, eval_now: bool = False) -> RoundMetrics:
        sys, cfg, tele = self.sys, self.cfg, self.obs
        t_round = time.perf_counter()
        tele.begin_round(i)
        ev0 = len(tele.events) if tele.enabled else 0
        # root of this round's span tree (schema v4): every stage/span
        # opened below records it as parent, so export/diff/dash can
        # reconstruct the full call hierarchy.  Entered manually — the
        # span must close just before RoundMetrics is built so eval and
        # aggregation land inside it.  The round's own glue between the
        # stages sits in plain spans (round.draws, round.uploads,
        # round.record, telemetry), so the stages keep their meaning.
        span_round = tele.span("round")
        span_round.__enter__()
        rf = (self.faults.for_round(i, sys.K)
              if self.faults is not None else None)

        with tele.stage("data"):
            images, labels, true = self._gather_round_batches()
        # the draws' span opens twice: the key split goes before sigma,
        # where its dispatch overlaps the batch's copy to the device
        with tele.span("round.draws"):
            self.key, kh, ka, kb = jax.random.split(self.key, 4)

        if tele.profile:
            self._profile_once("sigma_all", "sigma", self._sigma_all,
                               (self.params, images, labels), tele, i)
        sigma_slots = grad_slots = None
        with tele.stage("sigma"):
            sigma = self._sigma_all(self.params, images, labels)
            if self._sequence:
                sigma, sigma_slots = sigma
            sigma = tele.block(sigma)
        with tele.span("round.draws"):
            h = jax.random.exponential(kh, (sys.K, sys.N)) * 1e-5
            alpha = (jax.random.uniform(ka, (sys.K,)) < sys.eps
                     ).astype(jnp.float32)
            n_quarantined = 0
            if self._resilient:
                # quarantined devices sit the round out *before* the
                # solve, so no RB/power is allocated to them
                # (skip-with-decay)
                quarantined = self._quarantined_until > i
                n_quarantined = int(np.sum(quarantined))
                if n_quarantined:
                    alpha = alpha * jnp.asarray(~quarantined, jnp.float32)
            mask = jnp.ones_like(sigma)
            state = RoundState(h=h, alpha=alpha, sigma=sigma,
                               sigma_mask=mask)

        if cfg.scheme == "proposed" and i < cfg.warmup_rounds:
            # warmup: resource allocation as proposed, selection = all
            match = joint_mod.matching_mod.swap_matching(
                sys, state.h, state.alpha,
                evaluator=cfg.power_evaluator,
                mode=(cfg.matching_mode
                      if cfg.power_evaluator == "closed_form" else "auto"),
                telemetry=tele)
            with tele.stage("selection"):
                pass  # warmup selects everything; keep the stage present
            dec = joint_mod._finish(sys, match.rho, match.p,
                                    np.asarray(mask), state,
                                    feasible=match.feasible,
                                    swaps=match.swaps,
                                    unmatched=match.unmatched,
                                    telemetry=tele)
        elif cfg.scheme == "proposed":
            dec = joint_mod.proposed_scheme(
                sys, state, selection_method=cfg.selection_method,
                power_evaluator=cfg.power_evaluator, gp_steps=cfg.gp_steps,
                gp_step0=cfg.gp_step0, matching_mode=cfg.matching_mode,
                selection_chunk=cfg.selection_chunk, faults=rf,
                repair_infeasible=self._resilient, telemetry=tele)
        elif cfg.scheme.startswith("baseline"):
            dec = joint_mod.baseline_scheme(sys, state,
                                            int(cfg.scheme[-1]), key=kb,
                                            telemetry=tele)
        else:
            raise ValueError(cfg.scheme)

        with tele.span("round.uploads"):
            delta = jnp.asarray(dec.delta)
            matched = jnp.asarray(dec.rho.sum(axis=1) > 0, jnp.float32)
            uploaded = alpha * matched

        gap_proxy = None
        if self.monitor is not None:
            # mean training loss on the round batch under the PRE-update
            # params: the Lemma-2 gap proxy (L* offset cancels, see
            # repro.obs.monitor).  Read-only — numerics are untouched.
            flat_im = images.reshape((-1,) + images.shape[2:])
            gap_proxy = float(self.model.loss_fn(self.params, flat_im,
                                                 labels.reshape(-1)))

        if tele.profile:
            if cfg.local_steps > 1:
                self._profile_once(
                    "local_deltas", "local_grads", self._local_deltas,
                    (self.params, images, labels, delta,
                     jnp.asarray(cfg.lr)), tele, i)
            else:
                self._profile_once(
                    "local_grads", "local_grads", self._local_grads,
                    self._local_grads_args(images, labels, delta,
                                           uploaded), tele, i)
        with tele.stage("local_grads"):
            if cfg.local_steps > 1:
                grads = self._local_deltas(self.params, images, labels,
                                           delta, jnp.asarray(cfg.lr))
            else:
                grads = self._local_grads(*self._local_grads_args(
                    images, labels, delta, uploaded))
                if self._sequence or self._stream:
                    grads, grad_slots = grads
            grads = tele.block(grads)

        # ---- fault application + resilience policies ------------------
        with tele.span("round.uploads"):
            planned = np.asarray(uploaded) > 0
            surv = planned
            n_dropped = n_retries = 0
            if self._resilient:
                surv, n_dropped, n_retries = self._upload_outcomes(
                    i, rf, planned, tele)
                grads = self._inject_nan_uploads(rf, surv, grads, tele)
                surv, n_bad = self._screen_nonfinite(i, rf, surv, grads,
                                                     tele)
                n_dropped += n_bad

        g_norm_sq = None
        skipped_update = False
        with tele.stage("aggregate"):
            # IPW-consistent reweighting over the survivor set when it
            # differs from the planned one; else the clean eq. (19)
            renormalize = bool(self._resilient
                               and not np.array_equal(surv, planned))
            alpha_agg = uploaded
            if renormalize:
                alpha_agg = jnp.asarray(surv, jnp.float32)
                if self._res.dropout_policy == "resolve" and surv.any():
                    dec = self._resolve_for_survivors(state, alpha_agg,
                                                      dec, tele)
            # every IPW weight is 0 or at least |D̂_k|, so the realized
            # mass is positive exactly when an upload of a live device
            # survived: decided on the host, with no sync
            if not np.any(surv & self._ipw_live):
                # every upload was lost (or none was scheduled): applying
                # the zero/NaN step would still move Adam's state, so the
                # update is skipped and recorded instead
                skipped_update = True
                g_norm_sq = 0.0 if self.monitor is not None else None
                tele.fault("skip_update", injected=False,
                           reason="no_surviving_upload")
                reg0 = metrics_mod.get_default()
                if reg0.enabled:
                    reg0.counter("feel_rounds_skipped_total",
                                 "rounds whose optimizer update was "
                                 "skipped (no usable upload)").inc()
            elif self._stream:
                # grads is already the eq. (19) sum; it and the optimizer
                # state are donated to the step
                if self.monitor is not None:
                    g_norm_sq = float(sum(jnp.vdot(x, x)
                                          for x in jax.tree.leaves(grads)))
                params, self._opt_state = self._sum_step(
                    self.params, self._opt_state, grads)
                del grads
                self.params = tele.block(params)
            else:
                params, self.opt_state, g_hat = self._server_step(
                    sys, self.params, self.opt_state, grads, alpha_agg,
                    renormalize=renormalize)
                if self.monitor is not None:
                    g_norm_sq = float(sum(jnp.vdot(x, x)
                                          for x in jax.tree.leaves(g_hat)))
                self.params = tele.block(params)

        with tele.span("round.record"):
            sel = np.asarray(delta) > 0.5
            mislabeled = (np.asarray(labels) != true)
            frac_bad = (float(np.sum(sel & mislabeled))
                        / max(np.sum(sel), 1))
            self._cum = self._cum + dec.net_cost
            n_uploaded = int(np.sum(surv))
        acc = None
        if eval_now:
            with tele.stage("eval"):
                acc = tele.block(self.model.accuracy(
                    self.params, self.data.test_images,
                    self.data.test_labels))
        reg = metrics_mod.get_default()
        wall_s = time.perf_counter() - t_round
        if tele.enabled or reg.enabled:
            # work done only because a sink or registry is on
            with tele.span("telemetry"):
                e_cmp, e_com = self._energy_terms(dec)
                if tele.enabled:
                    self._record_round(tele, dec, sel, mislabeled,
                                       surv.astype(np.int64), acc, wall_s,
                                       e_cmp, e_com)
                if reg.enabled:
                    self._record_metrics(reg, dec, e_cmp, e_com,
                                         int(np.sum(sel)), n_uploaded,
                                         wall_s)
                if tele.enabled and reg.enabled:
                    tele.emit(reg.snapshot_event(round=i))
                if tele.enabled and sigma_slots is not None:
                    self._record_load(tele, sigma_slots, grad_slots, sel,
                                      np.asarray(uploaded) > 0,
                                      images.shape[-1])
        if self.monitor is not None:
            stage_s = None
            if tele.enabled:
                with tele.span("telemetry"):
                    stage_s = {e.stage: e.dur_s for e in tele.events[ev0:]
                               if isinstance(e, obs.StageEvent)}
            self.monitor.observe_round(
                i, gap=gap_proxy, g_norm_sq=g_norm_sq, eta=cfg.lr,
                delta_obj=float(dec.delta_obj), wall_s=wall_s,
                stage_s=stage_s)
        if (self._res.checkpoint_every > 0 and self._res.checkpoint_dir
                and (i + 1) % self._res.checkpoint_every == 0):
            path = self.save_checkpoint(next_round=i + 1)
            tele.fault("checkpoint", injected=False, path=path,
                       next_round=i + 1)
            if reg.enabled:
                reg.counter("feel_checkpoints_total",
                            "periodic trainer checkpoints written").inc()
        span_round.__exit__(None, None, None)
        return RoundMetrics(round=i, net_cost=dec.net_cost,
                            cum_net_cost=self._cum,
                            delta_obj=dec.delta_obj,
                            n_selected=int(np.sum(sel)),
                            n_uploaded=n_uploaded,
                            frac_mislabeled_selected=frac_bad,
                            test_acc=acc, n_dropped=n_dropped,
                            n_quarantined=n_quarantined,
                            n_retries=n_retries,
                            skipped_update=skipped_update,
                            fallbacks=dec.fallbacks,
                            feasible=bool(dec.feasible),
                            decision=dec)

    def _local_grads_args(self, images, labels, delta, uploaded):
        if self._stream:
            return (self.params, self.sys, uploaded, images, labels, delta)
        return (self.params, images, labels, delta)

    def _record_load(self, tele, sigma_slots, grad_slots, sel: np.ndarray,
                     uploaded: np.ndarray, seq_len: int) -> None:
        """The held experts' load of the round as the ``moe.load`` span's
        attributes: slots per layer and held expert of the scored
        sequences and of those with a nonzero eq. (19) weight (selected,
        of a live device that uploaded), the tokens behind each, and the
        row tile and operand dtype of the grouped products.  The counts
        come to the host here, under ``telemetry``."""
        weighted = sel & (uploaded & self._ipw_live)[:, None]   # (K, D̂)
        grad = np.zeros(np.shape(sigma_slots), np.int64)
        if grad_slots is not None:
            grad = np.einsum("kldh,kd->lh", np.asarray(grad_slots, np.int64),
                             weighted.astype(np.int64))
        with tele.span("moe.load",
                       sigma_slots=np.asarray(sigma_slots).tolist(),
                       grad_slots=grad.tolist(),
                       tokens_scored=int(sel.size * seq_len),
                       tokens_grad=int(weighted.sum() * seq_len),
                       **moe_mod.grouped_path(self.model.cfg,
                                              sel.shape[1] * seq_len)):
            pass

    def _profile_once(self, name: str, stage: str, fn, args, tele,
                      round_i: int) -> None:
        """Record one roofline ``ProfileEvent`` per (kernel, shapes)."""
        shapes = tuple(tuple(getattr(x, "shape", ()))
                       for x in jax.tree.leaves(args))
        key = (name, shapes)
        if key in self._profiled:
            return
        self._profiled.add(key)
        obs.profile_jitted(fn, args, name=name, stage=stage,
                           telemetry=tele, round=round_i)

    def _energy_terms(self, dec):
        """Per-device E^cmp (eq. 9) and E^com (eq. 16) for the chosen
        decision, as float64 numpy arrays."""
        rho_j = jnp.asarray(dec.rho, jnp.float32)
        p_j = jnp.asarray(dec.p, jnp.float32)
        e_cmp = np.asarray(cost_mod.energy_compute(self.sys), np.float64)
        e_com = np.asarray(cost_mod.energy_upload(self.sys, rho_j, p_j),
                           np.float64)
        return e_cmp, e_com

    def _record_round(self, tele, dec, sel: np.ndarray,
                      mislabeled: np.ndarray, uploaded: np.ndarray,
                      acc, wall_s: float, e_cmp: np.ndarray,
                      e_com: np.ndarray) -> None:
        """Emit the per-device (eqs. 16-18 terms) and round roll-up
        telemetry events.  Only called when the sink is enabled."""
        sys = self.sys
        c = np.asarray(sys.c, np.float64)
        q = np.asarray(sys.q, np.float64)
        m_k = sel.sum(axis=1)
        bad_k = (sel & mislabeled).sum(axis=1) / np.maximum(m_k, 1)
        tele.devices(
            energy_cmp_j=e_cmp.tolist(),
            energy_com_j=e_com.tolist(),
            cost=(c * (e_cmp + e_com)).tolist(),
            reward=(q * m_k).tolist(),
            selected=[int(v) for v in m_k],
            uploaded=[int(v) for v in uploaded],
            mislabel_frac=bad_k.tolist())
        tele.round_end(wall_s=wall_s, net_cost=float(dec.net_cost),
                       delta_obj=float(dec.delta_obj),
                       n_selected=int(sel.sum()),
                       n_uploaded=int(uploaded.sum()),
                       feasible=bool(dec.feasible),
                       test_acc=None if acc is None else float(acc))

    def _record_metrics(self, reg, dec, e_cmp: np.ndarray,
                        e_com: np.ndarray, n_selected: int,
                        n_uploaded: int, wall_s: float) -> None:
        """Per-round budget/outcome metrics (eqs. 16-18).  Only called
        when a real registry is installed."""
        reg.counter("feel_rounds_total", "completed FEEL rounds").inc()
        if not dec.feasible:
            reg.counter("feel_rounds_infeasible_total",
                        "rounds whose decision was infeasible").inc()
        reg.histogram("feel_round_wall_seconds",
                      "wall-clock per FEEL round").observe(wall_s)
        reg.counter("feel_energy_compute_joules_total",
                    "E^cmp (eq. 9) summed over devices and rounds").inc(
                        float(e_cmp.sum()))
        reg.counter("feel_energy_upload_joules_total",
                    "E^com (eq. 16) summed over devices and rounds").inc(
                        float(e_com.sum()))
        reg.counter("feel_samples_selected_total",
                    "samples selected for training").inc(n_selected)
        reg.counter("feel_samples_uploaded_total",
                    "device uploads aggregated").inc(n_uploaded)
        reg.gauge("feel_cum_net_cost",
                  "cumulative net cost (eq. 18) so far").set(self._cum)
        reg.gauge("feel_time_budget_seconds",
                  "per-round upload latency budget T (eq. 16)").set(
                      float(self.sys.T))

    # ------------------------------------------------------------------
    # fault-tolerance layer (docs/robustness.md)
    # ------------------------------------------------------------------
    @staticmethod
    def _count_injected(kind: str, n: int = 1) -> None:
        reg = metrics_mod.get_default()
        if reg.enabled and n:
            reg.counter("feel_faults_injected_total",
                        "faults injected by the FaultPlan, by kind").inc(
                            n, kind=kind)

    def _upload_outcomes(self, i: int, rf, planned: np.ndarray, tele):
        """Apply post-matching dropout and the straggler deadline with
        bounded retry + exponential backoff.  Returns the surviving
        upload mask plus (dropped, retry) counts."""
        res = self._res
        surv = planned.copy()
        n_dropped = n_retries = 0
        if rf is not None and rf.dropout.any():
            lost = planned & rf.dropout
            for k in np.flatnonzero(lost):
                tele.fault("dropout", injected=True, device=int(k))
            self._count_injected("dropout", int(lost.sum()))
            surv &= ~lost
            n_dropped += int(lost.sum())
        # upload completion per the eq. (8)+(16) latency model: compute
        # time tau_k plus the T-second upload slot, plus injected delay
        tau = np.asarray(cost_mod.compute_time(self.sys), np.float64)
        T = float(self.sys.T)
        deadline = (res.deadline_s if res.deadline_s is not None
                    else 1.5 * float(tau.max() + T))
        delays = rf.delay_s if rf is not None else np.zeros(self.sys.K)
        for k in np.flatnonzero(surv):
            # one span per attempted upload: carries the device index so
            # the Perfetto export lands it on that device's own track
            with tele.span("device.upload", device=int(k),
                           tau_s=float(tau[k])):
                if tau[k] + T + float(delays[k]) <= deadline:
                    continue
                injected = bool(rf is not None and rf.straggler[k])
                ok = False
                for t in range(1, res.max_retries + 1):
                    n_retries += 1
                    window = deadline * res.backoff_base ** t
                    d_t = (self.faults.retry_delay_s(i, int(k), t)
                           if self.faults is not None else 0.0)
                    tele.fault("retry", injected=injected, device=int(k),
                               attempt=t, delay_s=d_t, window_s=window)
                    if tau[k] + T + d_t <= window:
                        ok = True
                        break
                tele.fault("straggler", injected=injected, device=int(k),
                           delay_s=float(delays[k]), dropped=not ok,
                           retries=n_retries)
                if injected:
                    self._count_injected("straggler")
                if not ok:
                    surv[k] = False
                    n_dropped += 1
        reg = metrics_mod.get_default()
        if reg.enabled:
            if n_retries:
                reg.counter("feel_retries_total",
                            "straggler upload retry attempts").inc(
                                n_retries)
            if n_dropped:
                reg.counter("feel_dropouts_total",
                            "scheduled uploads lost mid-round").inc(
                                n_dropped)
        return surv, n_dropped, n_retries

    def _inject_nan_uploads(self, rf, surv: np.ndarray, grads, tele):
        """Corrupt the gradient upload of fault-plan-selected devices
        with NaNs (the defense then has to catch real NaNs)."""
        if rf is None or not bool((rf.nan_upload & surv).any()):
            return grads
        bad = rf.nan_upload & surv
        self._count_injected("nan_upload", int(bad.sum()))
        bad_j = jnp.asarray(bad)

        def corrupt(leaf):
            shape = (self.sys.K,) + (1,) * (leaf.ndim - 1)
            return jnp.where(bad_j.reshape(shape), jnp.nan, leaf)

        return jax.tree.map(corrupt, grads)

    def _screen_nonfinite(self, i: int, rf, surv: np.ndarray, grads,
                          tele):
        """Exclude non-finite uploads from aggregation and run the
        per-device quarantine (skip-with-decay) bookkeeping."""
        K = self.sys.K
        finite = np.ones(K, bool)
        for leaf in jax.tree.leaves(grads):
            ax = tuple(range(1, leaf.ndim))
            finite &= np.asarray(jnp.all(jnp.isfinite(leaf), axis=ax))
        bad = surv & ~finite
        clean = surv & finite
        res = self._res
        reg = metrics_mod.get_default()
        if bad.any() and reg.enabled:
            reg.counter("feel_nan_uploads_total",
                        "uploads excluded for non-finite values").inc(
                            int(bad.sum()))
        for k in np.flatnonzero(bad):
            self._strikes[k] += 1
            injected = bool(rf is not None and rf.nan_upload[k])
            tele.fault("nan_upload", injected=injected, device=int(k),
                       strikes=int(self._strikes[k]))
            if self._strikes[k] >= res.quarantine_threshold:
                until = i + 1 + res.quarantine_rounds
                self._quarantined_until[k] = until
                self._strikes[k] = 0
                tele.fault("quarantine", injected=False, device=int(k),
                           until_round=int(until))
                if reg.enabled:
                    reg.counter("feel_quarantines_total",
                                "devices quarantined for repeated "
                                "non-finite uploads").inc()
        # each clean upload decays one strike
        self._strikes[clean] = np.maximum(self._strikes[clean] - 1, 0)
        return surv & finite, int(bad.sum())

    def _resolve_for_survivors(self, state, surv_j, dec, tele):
        """Dropout policy "resolve": cheaply re-solve the RB assignment
        for the surviving devices so energy/cost accounting matches who
        actually uploaded.  Falls back to keeping the original decision
        (reweight-only) if the re-solve itself fails."""
        sys = self.sys
        try:
            match2 = joint_mod.matching_mod.swap_matching(
                sys, state.h, surv_j, evaluator="closed_form",
                mode=self.cfg.matching_mode, telemetry=tele)
        except Exception as e:  # keep the round alive
            tele.fault("solver_fail", injected=False, solver="matching",
                       reason=type(e).__name__, context="resolve")
            return dec
        tele.fault("fallback", injected=False, solver="matching",
                   to="resolve_survivors")
        reg = metrics_mod.get_default()
        if reg.enabled:
            reg.counter("feel_fallbacks_total",
                        "solver degradations by solver and target").inc(
                            1, solver="matching", to="resolve_survivors")
        return joint_mod._finish(
            sys, match2.rho, match2.p, dec.delta, state,
            feasible=match2.feasible, swaps=dec.swaps,
            unmatched=match2.unmatched,
            fallbacks=dec.fallbacks + ("resolve_survivors",),
            telemetry=tele)

    # ------------------------------------------------------------------
    # crash-safe checkpoint / resume (docs/robustness.md)
    # ------------------------------------------------------------------
    def save_checkpoint(self, path: Optional[str] = None,
                        next_round: int = 0) -> str:
        """Atomically persist everything ``resume`` needs to reproduce
        the uninterrupted trajectory bit-for-bit: params, optimizer
        state, both RNG streams, the round index, cumulative cost and
        the quarantine bookkeeping."""
        if path is None:
            if not self._res.checkpoint_dir:
                raise ValueError("no checkpoint path: pass one or set "
                                 "ResilienceConfig.checkpoint_dir")
            path = os.path.join(self._res.checkpoint_dir, CKPT_NAME)
        meta = {
            "next_round": int(next_round),
            "cum_net_cost": float(self._cum),
            "rng_state": self.rng.bit_generator.state,
            "jax_key": np.asarray(self.key).tolist(),
            "strikes": [int(v) for v in self._strikes],
            "quarantined_until": [int(v) for v in self._quarantined_until],
            "seed": int(self.cfg.seed),
            "fault_spec": (self.faults.to_dict()
                           if self.faults is not None else None),
        }
        ckpt_mod.save_pytree(path, {"params": self.params,
                                    "opt_state": self.opt_state},
                             metadata=meta)
        return path

    def resume(self, path: Optional[str] = None) -> int:
        """Restore a ``save_checkpoint`` state and return the round to
        continue from (``run`` picks it up automatically).  Because the
        fault plan, both RNG streams and the quarantine state are all
        restored, the resumed trajectory is bit-identical to the
        uninterrupted one."""
        if path is None:
            if not self._res.checkpoint_dir:
                raise ValueError("no checkpoint path: pass one or set "
                                 "ResilienceConfig.checkpoint_dir")
            path = self._res.checkpoint_dir
        if os.path.isdir(path):
            path = os.path.join(path, CKPT_NAME)
        like = {"params": self.params, "opt_state": self.opt_state}
        tree = ckpt_mod.load_pytree(path, like)
        meta = ckpt_mod.load_metadata(path)
        if meta is None:
            raise FileNotFoundError(f"{path}.meta.json missing — cannot "
                                    "resume without trainer metadata")
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        self._cum = float(meta["cum_net_cost"])
        rng = np.random.default_rng()
        rng.bit_generator.state = meta["rng_state"]
        self.rng = rng
        self.key = jnp.asarray(np.asarray(meta["jax_key"], np.uint32))
        self._strikes = np.asarray(meta["strikes"], np.int64)
        self._quarantined_until = np.asarray(meta["quarantined_until"],
                                             np.int64)
        self._start_round = int(meta["next_round"])
        self.obs.fault("resume", injected=False, path=path,
                       next_round=self._start_round)
        return self._start_round

    def run(self, rounds: int, verbose: bool = False) -> List[RoundMetrics]:
        """Run rounds ``[start, rounds)`` where ``start`` is 0 for a
        fresh trainer or the restored round index after ``resume()``."""
        out = []
        for i in range(self._start_round, rounds):
            eval_now = (i % self.cfg.eval_every == 0) or i == rounds - 1
            m = self.run_round(i, eval_now=eval_now)
            out.append(m)
            if verbose and eval_now:
                print(f"round {i:4d} acc={m.test_acc} "
                      f"cum_cost={m.cum_net_cost:.4f} sel={m.n_selected} "
                      f"bad_frac={m.frac_mislabeled_selected:.3f}")
        return out
