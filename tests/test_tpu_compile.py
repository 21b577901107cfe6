"""Compile-only checks against a described TPU v5e chip (no chip needed).

The TPU compiler refuses what interpret mode accepts: block shapes that
do not match XLA's tiling, too much fast memory, programs that do not
fit.  These tests compile the round's Pallas kernel and its jitted
sigma and local-gradient programs at the paper's §VI-A widths for one
v5e chip, so such a refusal shows up here and not on the chip.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports every test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import default_system
from repro.fed import FEELConfig, FEELTrainer
from repro.kernels import gradnorm
from repro.models import cnn

# K=10 devices x |D̂_k|=200 samples, the paper round; K=256 for a
# multi-block row grid.  84 = penultimate CNN width, 10 = classes.
PAPER_K, PAPER_D_HAT = 10, 200


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep such entries out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows,feat", [
    (PAPER_K * PAPER_D_HAT, 84),   # features h
    (PAPER_K * PAPER_D_HAT, 10),   # logit residuals p - y
    (256 * PAPER_D_HAT, 84),       # K=256: many row blocks
])
def test_rownorm2_compiles_for_v5e(one_chip, rows, feat):
    fn = jax.jit(functools.partial(gradnorm.rownorm2, interpret=False))
    compiled = fn.lower(_spec((rows, feat), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["sigma_all", "local_grads"])
def test_round_programs_compile_for_v5e(one_chip, monkeypatch, program):
    # the kernels pick compile-vs-interpret from the default backend,
    # which is the CPU here: steer it to the TPU branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cc = cnn.CNNConfig()
    params = cnn.init(jax.random.PRNGKey(0), cc)
    cfg = FEELConfig(sigma_method="last_layer_kernel", d_hat=PAPER_D_HAT)
    sys_ = default_system(K=PAPER_K, N=5, Q=2, D_hat=PAPER_D_HAT)
    tr = FEELTrainer(sys_, None, cnn, params, cfg)
    batch = (PAPER_K, PAPER_D_HAT)
    args = [jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                         params),
            _spec(batch + (cc.side, cc.side), jnp.float32, one_chip),
            _spec(batch, jnp.int32, one_chip)]
    if program == "local_grads":
        args.append(_spec(batch, jnp.float32, one_chip))
    fn = getattr(tr, f"_{program}")
    hlo = fn.lower(*args).compile().as_text()
    assert ("tpu_custom_call" in hlo) == (program == "sigma_all")


@pytest.mark.parametrize("program", ["sigma_all", "local_grads"])
def test_sequence_programs_compile_for_v5e(one_chip, monkeypatch, program):
    """DeepSeek-V2-Lite's sigma and device-by-device gradient programs at
    its published widths, one dense and one expert layer (8 of its 64
    experts held, an eighth of the vocabulary): the held share's grouped
    products lower for the TPU (``gmm``, and ``tgmm`` for the weights'
    gradient), fed bfloat16, with the held weights cast outside every
    while loop."""
    from repro.configs import get_config
    from repro.fed import rounds
    from repro.models import init_model
    from repro.models.control_lm import ControlTokenLM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(rounds, "device_bytes", lambda: 1)
    cfg = get_config("deepseek-v2-lite").scaled(
        n_layers=2, vocab=12800, experts_held=(0, 8), dtype="float32")
    K, d_hat, S = 2, 2, 128
    sys_ = default_system(K=K, N=1, Q=2, D_hat=d_hat)
    tr = FEELTrainer(sys_, None, ControlTokenLM(cfg), {"w": jnp.zeros(1)},
                     FEELConfig(d_hat=d_hat))
    assert tr._stream

    def spec(x):
        return _spec(x.shape, x.dtype, one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda k: init_model(k, cfg), jax.random.PRNGKey(0)))
    x = _spec((K, d_hat, S), jnp.int32, one_chip)
    labels = _spec((K, d_hat), jnp.int32, one_chip)
    if program == "sigma_all":
        lowered = tr._sigma_all.lower(params, x, labels)
    else:
        lowered = tr._local_grads.lower(
            params, jax.tree.map(spec, sys_),
            _spec((K,), jnp.float32, one_chip), x, labels,
            _spec((K, d_hat), jnp.float32, one_chip))
    hlo = lowered.compile().as_text()
    assert "gmm" in hlo and "tpu_custom_call" in hlo
    assert ("tgmm" in hlo) == (program == "local_grads")
    # the grouped products are fed bfloat16 rows and weights
    operands = _grouped_operand_types(hlo)
    assert len(operands) == (3 if program == "sigma_all" else 12)
    assert all(t.startswith("bf16[") for pair in operands for t in pair)
    # the held weights are cast once, not in a device's or layer's loop
    d, f = cfg.d_model, cfg.moe_d_ff
    weight_cast = re.compile(
        rf"= bf16\[[\d,]*({d},{f}|{f},{d})\]\S* convert\(")
    looped = [line for c in _while_bodies(hlo) for line in c
              if weight_cast.search(line)]
    assert not looped, looped


def _computations(hlo: str) -> dict:
    """The HLO text's computations: name -> instruction lines."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    return comps


def _grouped_operand_types(hlo: str) -> list:
    """(rows, weights) types of each ``gmm``/``tgmm`` custom call: its
    last two operands, after the scalar-prefetched group metadata."""
    types = dict(re.findall(r"^\s+(?:ROOT )?%(\S+) = (\S+?)\{", hlo, re.M))
    calls = re.findall(r"%t?gmm\.\d+ = \S+ custom-call\(([^)]*)\), "
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    return [tuple(types[o.split("%")[-1]] for o in args.split(",")[-2:])
            for args in calls]


def _while_bodies(hlo: str) -> list:
    """The instruction lines of every while body and of every
    computation a body calls, fused ones included."""
    comps = _computations(hlo)
    called = {c: set(re.findall(r"%([\w.\-]+)", "\n".join(lines)))
              & set(comps) for c, lines in comps.items()}
    todo = set(re.findall(r"body=%([\w.\-]+)", hlo))
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo |= called.get(c, set())
    return [comps[c] for c in seen if c in comps]
