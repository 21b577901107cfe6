"""FEEL runtime tests: Lemma-1 unbiasedness, selection behaviour on
mislabeled data, an end-to-end round, and the in-train FEEL step."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import convergence, default_system
from repro.core import joint
from repro.data import SyntheticImages, non_iid_split
from repro.fed import FEELConfig, FEELTrainer, per_sample_sigma
from repro.fed.server import aggregate_gradients
from repro.models import cnn


def test_aggregation_unbiased_lemma1():
    """Monte-Carlo check of Lemma 1: E[g_hat] == mean local gradient."""
    sys_ = default_system(K=6, N=3, Q=2, D_hat=4)
    key = jax.random.PRNGKey(0)
    grads = jax.random.normal(key, (6, 10))  # (K, P) fixed local grads
    truth = jnp.einsum("k,kp->p", sys_.D_hat / sys_.D_hat_total, grads)
    acc = jnp.zeros(10)
    M = 4000
    for i in range(M):
        a = (jax.random.uniform(jax.random.fold_in(key, i), (6,))
             < sys_.eps).astype(jnp.float32)
        acc = acc + aggregate_gradients(sys_, grads, a)
    err = float(jnp.max(jnp.abs(acc / M - truth)))
    scale = float(jnp.max(jnp.abs(truth)))
    assert err < 0.12 * max(scale, 1.0), (err, scale)


def test_sigma_full_vs_last_layer_ranking():
    """Both sigma modes must rank a mislabeled sample above a clean one
    once the model fits the clean data."""
    cc = cnn.CNNConfig(side=12)
    params = cnn.init(jax.random.PRNGKey(0), cc)
    data = SyntheticImages.make(64, side=12, seed=0)
    imgs = jnp.asarray(data.images)
    labels = jnp.asarray(data.labels)
    # overfit a few steps so predictions align with clean labels
    from repro import optim
    opt = optim.adam(3e-3)
    st = opt.init(params)
    step = jax.jit(lambda p, s: _sgd_step(p, s, imgs, labels, opt))
    for _ in range(60):
        params, st = step(params, st)
    bad_labels = labels.at[:8].set((labels[:8] + 1) % 10)
    for method in ("last_layer", "full"):
        sigma = per_sample_sigma(params, imgs[:16], bad_labels[:16],
                                 features_fn=cnn.features, method=method,
                                 loss_fn=cnn.loss_fn)
        bad = float(jnp.mean(sigma[:8]))
        good = float(jnp.mean(sigma[8:16]))
        assert bad > good, (method, bad, good)


def _sgd_step(params, st, imgs, labels, opt):
    g = jax.grad(cnn.loss_fn)(params, imgs, labels)
    upd, st = opt.update(g, st, params)
    from repro.optim import apply_updates
    return apply_updates(params, upd), st


@pytest.mark.slow
def test_feel_round_end_to_end():
    train = SyntheticImages.make(600, side=12, seed=0)
    test = SyntheticImages.make(200, side=12, seed=1)
    fd = non_iid_split(train, test, K=6, per_device=60,
                       mislabel_prop=0.1, seed=0)
    sys_ = default_system(K=6, N=3, Q=2, D_hat=16)
    cfg = FEELConfig(d_hat=16, gp_steps=80, eval_every=3)
    cc = cnn.CNNConfig(side=12)
    params = cnn.init(jax.random.PRNGKey(0), cc)
    model = types.SimpleNamespace(features=cnn.features, apply=cnn.apply,
                                  loss_fn=cnn.loss_fn,
                                  accuracy=cnn.accuracy)
    tr = FEELTrainer(sys_, fd, model, params, cfg)
    ms = tr.run(4)
    assert all(np.isfinite(m.net_cost) for m in ms)
    assert all(m.n_selected >= 6 for m in ms)  # >=1 per device (25)
    assert ms[0].test_acc is not None


@pytest.mark.slow
def test_fedavg_variant_runs():
    train = SyntheticImages.make(300, side=12, seed=0)
    test = SyntheticImages.make(100, side=12, seed=1)
    fd = non_iid_split(train, test, K=4, per_device=40,
                       mislabel_prop=0.1, seed=0)
    sys_ = default_system(K=4, N=2, Q=2, D_hat=10)
    cfg = FEELConfig(d_hat=10, local_steps=3, gp_steps=50, eval_every=10)
    cc = cnn.CNNConfig(side=12)
    params = cnn.init(jax.random.PRNGKey(0), cc)
    model = types.SimpleNamespace(features=cnn.features, apply=cnn.apply,
                                  loss_fn=cnn.loss_fn,
                                  accuracy=cnn.accuracy)
    tr = FEELTrainer(sys_, fd, model, params, cfg)
    ms = tr.run(2)
    assert np.isfinite(ms[-1].net_cost)


def test_feel_train_step_integration():
    """The in-jit FEEL integration: selection reduces to the exact
    solver's output, availability masks clients."""
    from repro.configs import smoke_config
    from repro.models import FeelIntegration, init_model, make_train_step
    from repro import optim
    cfg = smoke_config("llama3_2-3b")
    params = init_model(jax.random.PRNGKey(0), cfg)
    opt = optim.adamw(1e-3)
    st = opt.init(params)
    feel = FeelIntegration(n_clients=4)
    step = jax.jit(make_train_step(cfg, opt, feel=feel))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks,
             "alpha": jnp.ones((4,), jnp.float32)}
    p2, st2, m = step(params, st, batch)
    assert np.isfinite(float(m["loss"]))
    assert 0 < float(m["selected_frac"]) <= 1.0
    # all clients unavailable -> zero gradient signal -> params unchanged
    batch0 = dict(batch, alpha=jnp.zeros((4,), jnp.float32))
    p3, _, m0 = step(params, st, batch0)
    assert float(m0["loss"]) == 0.0


def test_lemma2_bound_on_quadratic():
    """On a strongly-convex quadratic with exact per-sample gradients,
    the Lemma-2 RHS is a valid upper bound of the expected next gap."""
    key = jax.random.PRNGKey(0)
    K, J, P = 4, 6, 5
    sys_ = default_system(K=K, N=2, Q=2, D_hat=J)
    A = jax.random.normal(key, (K, J, P)) * 0.5  # per-sample features

    def per_sample_grad(w):
        # l_kj = 0.5 ||w - a_kj||^2 -> grad = w - a_kj ; beta = 1
        return w[None, None, :] - A

    w = jnp.ones(P) * 2.0
    w_star = jnp.mean(A.reshape(-1, P), axis=0)

    def L(w):
        return 0.5 * float(jnp.mean(jnp.sum(
            (w[None, None] - A) ** 2, axis=-1)))

    eta, beta = 0.3, 1.0  # larger eta -> larger bound slack vs MC noise
    g = per_sample_grad(w)
    sigma = jnp.sum(g * g, axis=-1)  # (K, J)
    delta_sel = jnp.ones((K, J))
    gap = L(w) - L(w_star)
    g_true = jnp.mean(g.reshape(-1, P), axis=0)
    bound = convergence.one_round_bound(
        sys_, jnp.asarray(gap), jnp.sum(g_true ** 2), jnp.asarray(eta),
        jnp.asarray(beta), delta_sel, sigma)
    # Monte-Carlo the actual expected gap after one aggregated step
    gaps = []
    for i in range(1000):
        a = (jax.random.uniform(jax.random.fold_in(key, i), (K,))
             < sys_.eps).astype(jnp.float32)
        local = jnp.mean(g, axis=1)  # (K, P) full selection
        ghat = aggregate_gradients(sys_, local, a)
        gaps.append(L(w - eta * ghat) - L(w_star))
    se = float(np.std(gaps) / np.sqrt(len(gaps)))
    assert np.mean(gaps) <= float(bound) + 3 * se


def test_round_metrics_carry_the_applied_decision(monkeypatch):
    """In clean rounds ``RoundMetrics.decision`` is, array for array,
    what ``joint.proposed_scheme`` returned."""
    from tests.test_obs import _assert_decisions_equal, _tiny_trainer

    seen = []
    scheme = joint.proposed_scheme

    def recorded(*args, **kw):
        seen.append(scheme(*args, **kw))
        return seen[-1]

    monkeypatch.setattr(joint, "proposed_scheme", recorded)
    ms = _tiny_trainer().run(3)
    assert len(seen) == 3
    for m, dec in zip(ms, seen):
        assert m.fallbacks == () and not m.skipped_update
        _assert_decisions_equal(m.decision, dec)
        assert m.net_cost == dec.net_cost
        assert m.n_selected == int(np.sum(dec.delta > 0.5))


@pytest.mark.parametrize("path", ["clean", "renormalized"])
@pytest.mark.parametrize("optimizer", ["adam", "sgd", "momentum",
                                       "adafactor"])
def test_server_step_matches_eager_sequence(optimizer, path):
    """Over three rounds the fused server step gives the parameters and
    optimizer state of the eager aggregate_gradients -> opt.update ->
    apply_updates sequence; on the survivor path one upload, lost and
    NaN, is scrubbed before the renormalized sum."""
    from repro import optim
    from tests.test_obs import _tiny_trainer

    tr = _tiny_trainer(optimizer=optimizer)
    sys_, K = tr.sys, tr.sys.K
    renormalize = path == "renormalized"
    fused = (tr.params, tr.opt_state)
    eager = fused
    for r in range(3):
        leaves, treedef = jax.tree.flatten(tr.params)
        keys = jax.random.split(jax.random.PRNGKey(r), len(leaves))
        grads = treedef.unflatten([
            jax.random.normal(k, (K,) + x.shape, x.dtype)
            for k, x in zip(keys, leaves)])
        alpha = jnp.ones((K,), jnp.float32)
        if renormalize:
            alpha = alpha.at[r % K].set(0.0)
            grads = jax.tree.map(lambda g: g.at[r % K].set(jnp.nan), grads)
        p, s, _ = tr._server_step(sys_, *fused, grads, alpha,
                                  renormalize=renormalize)
        fused = (p, s)

        kept = grads
        if renormalize:
            kept = jax.tree.map(
                lambda g: jnp.where(
                    (alpha > 0).reshape((K,) + (1,) * (g.ndim - 1)), g, 0.0),
                grads)
        g_hat = aggregate_gradients(sys_, kept, alpha,
                                    renormalize=renormalize)
        updates, s = tr.opt.update(g_hat, eager[1], eager[0])
        eager = (optim.apply_updates(eager[0], updates), s)

    assert jax.tree.structure(fused) == jax.tree.structure(eager)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(eager)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        if np.issubdtype(a.dtype, np.integer):   # Adam's count
            np.testing.assert_array_equal(a, b)
        else:
            # float32: rtol 1e-5, and near zero the rounding of the
            # leaf's largest terms (a sum's order may differ in one program)
            assert np.isfinite(a).all()
            np.testing.assert_allclose(a, b, rtol=1e-5,
                                       atol=1e-6 * np.abs(b).max())
