"""Training driver.

Runs real steps (allocating parameters) for any --arch at any scale
that fits the host; on TPU pods, pair with make_production_mesh.  The
FEEL integration (per-sample sigma scoring + exact Problem-4 selection
+ eq.-(19) IPW aggregation across the client/data axis) is on by
default — this is the paper's technique applied to LM training.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --smoke --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_config, smoke_config
from ..data.synthetic import synthetic_lm_batch
from ..models import FeelIntegration, init_model, make_train_step, param_count
from .compile_cache import enable_compile_cache
from .shapes import make_optimizer


def synth_batch(cfg, key, batch, seq, n_clients, feel, eps=0.8):
    if cfg.modality == "text":
        b = synthetic_lm_batch(key, batch, seq, cfg.vocab)
    elif cfg.modality == "vlm":
        k1, k2 = jax.random.split(key)
        b = {"embeds": jax.random.normal(k1, (batch, seq, cfg.d_model),
                                         cfg.act_dtype),
             "positions": jnp.broadcast_to(
                 jnp.arange(seq)[None, None, :],
                 (batch, 3, seq)).astype(jnp.int32),
             "labels": jax.random.randint(k2, (batch, seq), 0, cfg.vocab)}
    else:
        k1, = jax.random.split(key, 1)
        t = jax.random.randint(k1, (batch, cfg.n_codebooks, seq + 1),
                               0, cfg.vocab)
        b = {"tokens": t[..., :-1], "labels": t[..., 1:]}
    if feel:
        ka = jax.random.fold_in(key, 7)
        b["alpha"] = (jax.random.uniform(ka, (n_clients,)) < eps
                      ).astype(jnp.float32)
    return b


def run(arch: str, steps: int, batch: int, seq: int, smoke: bool,
        feel: bool = True, n_clients: int = 4, log_every: int = 5,
        seed: int = 0):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    key = jax.random.PRNGKey(seed)
    params = init_model(key, cfg)
    print(f"arch={cfg.name} params={param_count(params):,} feel={feel}")
    opt = make_optimizer(cfg)
    opt_state = opt.init(params)
    feel_cfg = FeelIntegration(n_clients=n_clients) if feel else None
    step_fn = jax.jit(make_train_step(cfg, opt, feel=feel_cfg),
                      donate_argnums=(0, 1))
    losses = []
    t0 = time.time()
    for i in range(steps):
        b = synth_batch(cfg, jax.random.fold_in(key, 1000 + i), batch, seq,
                        n_clients, feel)
        params, opt_state, metrics = step_fn(params, opt_state, b)
        losses.append(float(metrics["loss"]))
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:4d} loss={losses[-1]:.4f} "
                  f"sel={float(metrics['selected_frac']):.3f} "
                  f"t={time.time() - t0:.1f}s", flush=True)
    assert np.isfinite(losses[-1]), "training diverged"
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--no-feel", action="store_true")
    ap.add_argument("--clients", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    run(args.arch, args.steps, args.batch, args.seq, args.smoke,
        feel=not args.no_feel, n_clients=args.clients)


if __name__ == "__main__":
    main()
