"""Launch-layer tests: sharding specs, collective parser, host-mesh
lowering of a smoke config (the 512-device production meshes are
exercised by the dry-run sweep, recorded in EXPERIMENTS.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.launch import make_host_mesh
from repro.launch.dryrun import collective_bytes
from repro.launch.sharding import _param_spec


def test_param_spec_megatron_pairing():
    kw = dict(model=16, data=16, data_ax=("data",), skip_leading=False,
              is_expert=False)
    # column-parallel: out features over model
    assert _param_spec("wq", (4096, 4096), **kw) == P(None, "model")
    assert _param_spec("w_gate", (4096, 16384), **kw) == P(None, "model")
    # row-parallel: contraction over model, ZeRO data on the out dim
    assert _param_spec("wo", (4096, 4096), **kw) == P("model", ("data",))
    assert _param_spec("w_down", (16384, 4096), **kw) \
        == P("model", ("data",))
    # embed: vocab-parallel + data on features
    assert _param_spec("embed", (128000, 4096), **kw) \
        == P("model", ("data",))
    # norms replicate
    assert _param_spec("ln1", (4096,), **kw) == P(None)
    # non-divisible dims stay unsharded
    assert _param_spec("wk", (4096, 24), **kw) == P(None, None)


def test_param_spec_scan_stacked_and_experts():
    kw = dict(model=16, data=16, data_ax=("data",), skip_leading=True,
              is_expert=False)
    assert _param_spec("wq", (28, 4096, 4096), **kw) \
        == P(None, None, "model")
    kw["is_expert"] = True
    # E divisible by data*model -> joint expert sharding (1 expert/chip;
    # EXPERIMENTS.md §Perf pair B iter 2)
    assert _param_spec("w_gate", (28, 256, 7168, 2048), **kw) \
        == P(None, ("data", "model"), None, None)
    # E=160: fallback expert-parallel + ZeRO on the per-expert features
    assert _param_spec("w_gate", (28, 160, 5120, 1536), **kw) \
        == P(None, "model", None, ("data",))


def test_collective_parser():
    hlo = """
  %all-reduce.1 = f32[128,1024]{1,0} all-reduce(%x), replica_groups={}
  %ag = bf16[64,512]{1,0} all-gather(%y), dimensions={0}
  %tup = (f32[10,10]{1,0}, f32[10,10]{1,0}) all-to-all(%a, %b)
  %not_a_collective = f32[5,5]{1,0} add(%p, %q)
  %rs.7 = bf16[32]{0} reduce-scatter(%z), dimensions={0}
  %cp = u32[16]{0} collective-permute-start(%w)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 128 * 1024 * 4
    assert got["all-gather"] == 64 * 512 * 2
    assert got["all-to-all"] == 2 * 10 * 10 * 4
    assert got["reduce-scatter"] == 32 * 2
    assert got["collective-permute"] == 16 * 4
    assert got["count"] == 5


@pytest.mark.slow
def test_host_mesh_lowering_smoke():
    """A reduced config lowers+compiles under a real (1x1) mesh with the
    production sharding rules — the same code path the 512-dev dry-run
    uses."""
    from repro.launch import sharding as sh
    from repro.models import init_model, make_train_step
    from repro.launch.shapes import make_optimizer
    cfg = smoke_config("llama3_2-3b")
    mesh = make_host_mesh(1, 1)
    params_abs = jax.eval_shape(lambda k: init_model(k, cfg),
                                jax.random.PRNGKey(0))
    p_sh = sh.param_shardings(mesh, params_abs, cfg)
    # every leaf got a NamedSharding with a valid spec
    for leaf in jax.tree.leaves(p_sh):
        assert leaf.mesh is mesh

    opt = make_optimizer(cfg)
    step = make_train_step(cfg, opt)
    toks = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    opt_abs = jax.eval_shape(opt.init, params_abs)
    with mesh, sh.with_mesh_constraints(mesh):
        lowered = jax.jit(step).lower(params_abs, opt_abs, batch)
        compiled = lowered.compile()
    assert compiled.cost_analysis()["flops"] > 0


def test_shapes_applicability_gates():
    from repro.launch.shapes import LONG_OK, applicable
    assert applicable("falcon-mamba-7b", "long_500k")
    assert applicable("gemma3-12b", "long_500k")
    assert not applicable("command-r-35b", "long_500k")
    assert not applicable("deepseek-v3-671b", "long_500k")
    assert all(applicable(a, "train_4k") for a in LONG_OK)


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_dir_is_fixed(monkeypatch, tmp_path, env_set):
    """The environment's cache directory wins; otherwise the checkout's
    ``.jax_cache`` — never a temporary or per-run path."""
    from pathlib import Path

    from repro.launch import compile_cache

    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.CACHE_DIR == repo / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_set:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(compile_cache.CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
