"""DeepSeek-V2-Lite's block in the FEEL round, at small widths on the CPU:
the held-expert share of an expert layer, YaRN, the sequence sigma, the
device-by-device eq. (19) sum, and three rounds of ``FEELTrainer`` on
the benchmark's model module against its plain reference."""
import dataclasses
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, smoke_config
from repro.core import default_system
from repro.fed import FEELConfig, FEELTrainer, ResilienceConfig
from repro.fed import client, rounds
from repro.fed.server import aggregate_gradients
from repro.models import init_model
from repro.models.control_lm import ControlTokenLM
from repro.models.layers import yarn_inv_freq, yarn_mscale
from repro.models.mla import softmax_scale
from repro.models import moe
from repro.models.moe import held_expert_ffn, init_moe, route

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
HIGHEST = jax.lax.Precision.HIGHEST


def _layer_cfg(**kw):
    """Expert layer of 8 routed experts, top-3, one shared expert."""
    return dataclasses.replace(
        smoke_config("deepseek-v2-lite"), n_experts=8, topk=3,
        n_shared_experts=1, dtype="float32", **kw)


def _dense_part(cfg, p, x, experts):
    """The layer's part from ``experts`` (ids into p's weights), each over
    every token with its gate, plus the shared experts."""
    xf = x.reshape(-1, x.shape[-1])
    logits = jnp.dot(xf, p["router"], precision=HIGHEST)
    gates, idx = route(cfg, logits)
    y = jnp.zeros_like(xf)
    for e in experts:
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        h = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
        y = y + g[:, None] * (h @ p["w_down"][e])
    return y.reshape(x.shape)


def _shared(p, x):
    s = p["shared"]
    return (jax.nn.silu(x @ s["w_gate"]) * (x @ s["w_up"])) @ s["w_down"]


def test_held_shares_add_up_to_the_uncut_layer():
    """4 chips holding 2 of 8 experts each: the four parts, the shared
    experts counted once, are the uncut layer."""
    cfg = _layer_cfg()
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    whole = _dense_part(cfg, p, x, range(8)) + _shared(p, x)
    total, slots = 0.0, 0
    for chip in range(4):
        share = dataclasses.replace(cfg, experts_held=(2 * chip, 2))
        ps = dict(p, **{k: p[k][2 * chip:2 * chip + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        y, s = held_expert_ffn(share, ps, x)
        total = total + y - _shared(p, x)
        slots += int(s.sum())
    total = total + _shared(p, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    assert slots == 2 * 16 * cfg.topk


def test_held_share_over_partial_tiles_matches_dense():
    """Widths past one 512-wide tile, neither a multiple of it: the held
    share and its weights' gradient equal the dense computation."""
    cfg = _layer_cfg(d_model=640, moe_d_ff=704)
    p = init_moe(jax.random.PRNGKey(2), cfg, jnp.float32)
    ph = dict(p, **{k: p[k][2:6] for k in ("w_gate", "w_up", "w_down")})
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    share = dataclasses.replace(cfg, experts_held=(2, 4))

    def held(ph):
        return held_expert_ffn(share, ph, x)[0]

    def dense(ph):
        full = dict(p, **{k: p[k].at[2:6].set(ph[k])
                          for k in ("w_gate", "w_up", "w_down")})
        return _dense_part(cfg, full, x, range(2, 6)) + _shared(p, x)

    np.testing.assert_allclose(np.asarray(held(ph)), np.asarray(dense(ph)),
                               rtol=2e-4, atol=2e-4)
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    g_held = jax.grad(lambda q: jnp.sum(held(q) * ct))(ph)
    g_dense = jax.grad(lambda q: jnp.sum(dense(q) * ct))(ph)
    for k in ("w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(np.asarray(g_held[k]),
                                   np.asarray(g_dense[k]),
                                   rtol=2e-4, atol=2e-4)


def _rounded_dense(x, w, dy, sizes):
    """Each group's rows times its weights, its input gradient and its
    weights' gradient as dense products of bfloat16-rounded operands,
    multiplied and summed in float32."""
    def bf(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    ys, dxs, dws, start = [], [], [], 0
    for g, n in enumerate(sizes):
        xg, dyg = bf(x[start:start + n]), bf(dy[start:start + n])
        ys.append(jnp.dot(xg, bf(w[g]), precision=HIGHEST))
        dxs.append(jnp.dot(dyg, bf(w[g]).T, precision=HIGHEST))
        dws.append(jnp.dot(xg.T, dyg, precision=HIGHEST))
        start += n
    return jnp.concatenate(ys), jnp.concatenate(dxs), jnp.stack(dws)


@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("name", ["w_gate", "w_up", "w_down"])
def test_bfloat16_grouped_products_match_rounded_dense(tm, name):
    """Fed bfloat16 (the TPU's path at JAX's default matmul precision),
    the grouped product of each of the three weights, its input
    gradient and its weights' gradient equal dense products of the same
    rounded operands with float32 accumulation, all in float32: groups
    over partial tiles, one group empty, widths past one 512 tile."""
    cfg = _layer_cfg(d_model=640, moe_d_ff=704)
    w = init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)[name][:4]
    sizes = [tm + 37, 0, 2 * tm - 5, 90]
    n = sum(sizes)
    rows = -(-n // tm) * tm
    kx, kdy = jax.random.split(jax.random.PRNGKey(6))
    x = jax.random.normal(kx, (rows, w.shape[1]))
    dy = jnp.where(jnp.arange(rows)[:, None] < n,
                   jax.random.normal(kdy, (rows, w.shape[2])), 0.0)
    group_sizes = jnp.asarray(sizes, jnp.int32)

    def grouped(dtype):
        y, vjp = jax.vjp(
            lambda a, b: moe._grouped(a, b, group_sizes, tm, dtype), x, w)
        return (y,) + vjp(dy)

    y, dx, dw = grouped(jnp.bfloat16)
    assert y.dtype == dx.dtype == dw.dtype == jnp.float32
    want = _rounded_dense(x, w, dy, sizes)
    for got, ref in zip((y[:n], dx[:n], dw), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(dw[1]), 0.0)
    # float32 operands read differently: the check sees the rounding
    y32 = grouped(jnp.float32)[0]
    assert float(jnp.max(jnp.abs(y32[:n] - want[0]))) > 1e-3


@pytest.mark.parametrize("G,K,E,tm", [
    (4096, 6, 64, 256),   # dsv2lite_k10: 8 sequences of 512, top-6 of 64
    (8192, 6, 64, 512),
    (2 * 16, 3, 8, 128),  # the layer tests here
    (2 * 8, 3, 8, 128),   # the trainer tests here
    (2 * 128, 6, 64, 128),  # the v5e compile test's pass
])
def test_row_tile_follows_the_expected_expert_load(G, K, E, tm):
    assert moe.row_tile(G * K, E) == tm


def test_operand_dtype_follows_backend_and_precision(monkeypatch):
    """float32 off the TPU; on it bfloat16 at JAX's default matmul
    precision, float32 where the setting asks for float32 or more."""
    assert moe.operand_dtype() == jnp.float32
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.operand_dtype() == jnp.bfloat16
    with jax.default_matmul_precision("bfloat16"):
        assert moe.operand_dtype() == jnp.bfloat16
    for asks in ("float32", "highest", "tensorfloat32"):
        with jax.default_matmul_precision(asks):
            assert moe.operand_dtype() == jnp.float32


def test_share_drops_no_token_under_a_skewed_router():
    """Every token routes to the held experts 0-2: the share computes all
    2·16·3 slots, counted per sequence, and matches the dense part."""
    cfg = _layer_cfg(experts_held=(0, 4))
    p = init_moe(jax.random.PRNGKey(2), cfg, jnp.float32)
    # positive inputs, and only experts 0-2 have (positive) router rows
    p["router"] = jnp.zeros_like(p["router"]).at[:, :3].set(
        jnp.abs(p["router"][:, :3]) + 0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (2, 16, cfg.d_model)))
    y, slots = held_expert_ffn(cfg, p, x)
    _, idx = route(cfg, jnp.dot(x.reshape(-1, cfg.d_model), p["router"],
                                precision=HIGHEST))
    routed = np.asarray(idx) < 4
    assert routed.all()                       # the skew holds
    assert np.asarray(slots).sum(axis=1).tolist() == [16 * 3, 16 * 3]
    np.testing.assert_array_equal(
        np.asarray(slots).sum(axis=0),
        np.bincount(np.asarray(idx)[routed], minlength=4))
    want = _dense_part(cfg, p, x, range(4)) + _shared(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_gates_are_not_renormalized_without_norm_topk_prob():
    cfg = _layer_cfg(norm_topk_prob=False, routed_scaling_factor=2.5)
    logits = jax.random.normal(jax.random.PRNGKey(4), (32, 8))
    gates, idx = route(cfg, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    want = 2.5 * jnp.take_along_axis(probs, idx, axis=-1)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want),
                               rtol=1e-6)
    assert float(jnp.max(jnp.sum(gates, -1))) < 2.5
    renorm, _ = route(dataclasses.replace(cfg, norm_topk_prob=True), logits)
    np.testing.assert_allclose(np.asarray(jnp.sum(renorm, -1)), 2.5,
                               rtol=1e-6)


def test_yarn_at_deepseek_v2_lite_values():
    """The inverse frequencies, the cos/sin scale and the softmax scale,
    written out from DeepSeek-V2's formulas at its published values."""
    cfg = get_config("deepseek-v2-lite")
    dim, base, factor = 64, 1e4, 40.0

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    inv = yarn_inv_freq(dim, base, factor, 4096, 32.0, 1.0)
    for i in range(dim // 2):
        extra = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = extra / factor * ramp + extra * (1 - ramp)
        assert inv[i] == pytest.approx(want, rel=1e-6)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert yarn_mscale(1.0, 0.707) == 1.0
    assert softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.26080 ** 2, rel=1e-4)
    assert softmax_scale(cfg) * 192 ** 0.5 == pytest.approx(1.5896,
                                                            abs=1e-4)


def test_gram_sigma_is_the_head_gradient_norm():
    """sigma in Gram form equals the squared norm of the head's gradient
    of the sequence's mean loss, taken by autodiff."""
    cfg = dataclasses.replace(smoke_config("deepseek-v2-lite"),
                              dtype="float32")
    model = ControlTokenLM(cfg)
    params = init_model(jax.random.PRNGKey(5), model.cfg)
    x = jax.random.randint(jax.random.PRNGKey(6), (3, 12), 10, cfg.vocab)
    y = jnp.asarray([1, 2, 3])
    h, r, _ = model.head_residuals(params, x, y)
    sigma = client.sequence_sigma(h, r)
    head_grad = jax.jit(lambda p, xb, yb: jax.grad(
        lambda q: model.per_sample_loss(q, xb, yb)[0][0])(p)["lm_head"])
    for b in range(3):
        g = head_grad(params, x[b:b + 1], y[b:b + 1])
        assert float(sigma[b]) == pytest.approx(
            float(jnp.sum(g * g)), rel=1e-4)


def _lm_trainer(K=3, d_hat=2, S=8, **kw):
    cfg = dataclasses.replace(smoke_config("deepseek-v2-lite"),
                              dtype="float32", n_experts=8, topk=3,
                              experts_held=(2, 4))
    model = ControlTokenLM(cfg)
    params = init_model(jax.random.PRNGKey(7), cfg)
    sys_ = default_system(K=K, N=2, Q=2, D_hat=d_hat)
    tr = FEELTrainer(sys_, None, model, params,
                     FEELConfig(d_hat=d_hat, lr=1e-3), **kw)
    x = jax.random.randint(jax.random.PRNGKey(8), (K, d_hat, S), 10,
                           cfg.vocab)
    y = jnp.tile(jnp.arange(K)[:, None], (1, d_hat))
    return tr, sys_, x, y


def _cnn_trainer(K=3, d_hat=2):
    from repro.models import cnn
    cc = cnn.CNNConfig(side=12)
    params = cnn.init(jax.random.PRNGKey(7), cc)
    sys_ = default_system(K=K, N=2, Q=2, D_hat=d_hat)
    tr = FEELTrainer(sys_, None, cnn, params, FEELConfig(d_hat=d_hat))
    x = jax.random.uniform(jax.random.PRNGKey(8), (K, d_hat, 12, 12))
    y = jax.random.randint(jax.random.PRNGKey(9), (K, d_hat), 0, 10)
    return tr, sys_, x, y


@pytest.mark.parametrize("kind", ["lm", "cnn"])
def test_scan_sum_equals_the_stacked_aggregate(monkeypatch, kind):
    """The device-by-device eq. (19) sum equals the per-device gradient
    stack through ``aggregate_gradients``; a device of weight 0 adds
    nothing and counts no slots."""
    make = _lm_trainer if kind == "lm" else _cnn_trainer
    plain, sys_, x, y = make()
    monkeypatch.setattr(rounds, "GRAD_TREES_SHARE", 0.0)
    streamed, _, _, _ = make()
    assert streamed._stream and not plain._stream
    delta = jnp.asarray([[1.0, 0.0], [1.0, 1.0], [0.0, 1.0]])
    alpha = jnp.asarray([1.0, 0.0, 1.0])
    stack = plain._local_grads(plain.params, x, y, delta)
    if kind == "lm":
        stack, slots = stack
    want = aggregate_gradients(sys_, stack, alpha)
    got, got_slots = streamed._local_grads(streamed.params, sys_, alpha, x,
                                           y, delta)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    if kind == "lm":
        np.testing.assert_array_equal(np.asarray(got_slots[1]), 0)
        np.testing.assert_array_equal(np.asarray(got_slots)[[0, 2]],
                                      np.asarray(slots)[[0, 2]])
    else:
        assert got_slots is None


def test_resilience_over_the_share_names_the_size(monkeypatch):
    monkeypatch.setattr(rounds, "GRAD_TREES_SHARE", 0.0)
    with pytest.raises(ValueError, match=r"gradient trees take .* GB"):
        _lm_trainer(resilience=ResilienceConfig())


def test_moe_load_span_counts_the_routed_slots(monkeypatch):
    """Traced, a round records ``moe.load`` under ``telemetry``: the
    sigma pass's slots are the held assignments of every scored token
    (as the layer counts them: see the skewed-router test), the gradient
    pass's those of the sequences with a nonzero eq. (19) weight."""
    monkeypatch.setattr(rounds, "GRAD_TREES_SHARE", 0.0)
    tele = obs.Telemetry()
    tr, sys_, x, y = _lm_trainer(K=4, telemetry=tele)
    tr.data = _dataset(np.asarray(x), np.asarray(y))
    m = tr.run_round(0)
    from repro.obs.spans import build_tree
    roots, _ = build_tree(tele.events)
    nodes = [n for r in roots for n in r.walk() if n.name == "moe.load"]
    assert len(nodes) == 1
    tel = [n for r in roots for n in r.walk() if n.name == "telemetry"]
    assert any(nodes[0] in list(t.walk()) for t in tel)
    a = nodes[0].attrs
    n_moe = tr.model.cfg.n_layers - tr.model.cfg.first_dense
    assert a["tokens_scored"] == 4 * 2 * 8
    # every device scores all of its sequences: the batch is the data
    _, slots = tr._sigma_all(jax.tree.map(jnp.asarray, _params0(tr)), x, y)
    np.testing.assert_array_equal(np.asarray(a["sigma_slots"]),
                                  np.asarray(slots))
    assert np.shape(a["grad_slots"]) == (n_moe, 4)
    weighted = a["tokens_grad"] // 8
    assert weighted == sum(
        int(np.sum(m.decision.delta[k] > 0.5))
        for k in range(4) if m.decision.rho[k].sum() > 0
        and tr._ipw_live[k]) or m.n_uploaded < 4
    assert np.sum(a["grad_slots"]) <= a["tokens_grad"] * 3
    assert np.sum(a["grad_slots"]) <= np.sum(a["sigma_slots"])
    assert (a["row_tile"], a["operand_dtype"]) == (128, "float32")


def _params0(tr):
    return init_model(jax.random.PRNGKey(7), tr.model.cfg)


def _dataset(x, y):
    from repro.data.federated import FederatedDataset
    K = x.shape[0]
    return FederatedDataset(device_images=list(x), device_labels=list(y),
                            device_true=list(y),
                            test_images=np.zeros((0, x.shape[2]), np.int32),
                            test_labels=np.zeros((0,), np.int32),
                            num_classes=K)


# ------------------------------------------- against the bench reference

def _bench():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_models_deepseek_v2_lite_test",
        os.path.join(BENCH, "models", "deepseek_v2_lite.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small_cell_cfg():
    with open(os.path.join(BENCH, "configs",
                           "deepseek_v2_lite_k10.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
               intermediate_size=256, moe_intermediate_size=64,
               vocab_size=256, seq_len=12, num_hidden_layers=2, K=4, N=2,
               per_device=6, d_hat=3, gp_steps=20, n_routed_experts=4)
    cfg["deployment"] = dict(cfg["deployment"], router_experts=16,
                             experts_held_first=4)
    return cfg


@pytest.mark.parametrize("stream", [False, True])
def test_three_rounds_agree_with_the_bench_reference(monkeypatch, stream):
    """Three ``FEELTrainer`` rounds on the model module's data and
    weights against its plain reference run on the program's selection:
    sigma, powers, the first g_hat and the parameters."""
    if stream:
        monkeypatch.setattr(rounds, "GRAD_TREES_SHARE", 0.0)
    m = _bench()
    import correct
    import inputs
    import reference
    import run as bench_run

    cfg = _small_cell_cfg()
    with open(os.path.join(BENCH, "traffic", "clean.json")) as f:
        traffic = json.load(f)
    cell = bench_run.Cell("dsv2lite_k10", 1, cfg, traffic, [], m)
    sub = inputs.seeds(2147483901)
    data, params0, trainer = bench_run.build(cell, sub, obs.NULL)
    assert trainer._stream == stream
    scored = []
    sigma_all = trainer._sigma_all
    trainer._sigma_all = lambda *a: scored.append(sigma_all(*a)) \
        or scored[-1]
    observed = bench_run.first_rounds(trainer, params0)
    ref = reference.Reference(cfg, traffic, sub, m).run(
        data, params0, rounds=bench_run.SETUP_ROUNDS,
        selections=observed.delta)
    for got, want in zip(scored, ref.rounds):
        np.testing.assert_allclose(np.asarray(got[0]), want.sigma,
                                   rtol=1e-4)
    numbers = correct.compare(observed, ref)
    assert numbers["power_gap"] < 1e-5
    assert numbers["upload_diff"] == 0
    assert numbers["grad_gap"] < 1e-4
    assert numbers["update_gap"] < 1e-3
    assert observed.first_grad is not None
