"""Per-sample gradient-norm scoring kernel (the sigma_{k,j} producer).

For a linear head  logits = h W + b  with CE loss, the exact per-sample
gradient-norm^2 of the head is

    sigma_j = ||p_j - y_j||^2 * (||h_j||^2 + 1)

so the whole score reduces to two row-wise squared norms.  This kernel
computes row-wise sum-of-squares with feature-dim tiling: grid
(n_row_blocks, n_feat_blocks) with the feature axis minor-most and a
(block_rows, 128) VMEM accumulator carried across the feature sweep —
one HBM pass over the matrix, VPU-only (no MXU), (8, 128)-aligned
tiles.  Each feature block is folded into the accumulator 128 lanes at
a time (elementwise adds); after the last block one transpose puts the
row sums on lanes, so the output is a lane-dense (1, n_rows) row.
Mosaic refuses a 1-D output block here: XLA tiles an f32[n] array by
1024 and a (block_rows,) block by 256.

The fused wrapper ``gradnorm_sigma`` runs it over the feature matrix h
and the logit-residual matrix d and combines:
    sigma = (rownorm2(h) + 1) * rownorm2(d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

LANES = 128
DEFAULT_BLOCK_ROWS = 256  # multiple of LANES: rows become output lanes
DEFAULT_BLOCK_FEAT = 512  # multiple of LANES


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _rownorm2_kernel(x_ref, o_ref, acc_ref):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (block_rows, block_feat)
    sq = x * x
    acc = acc_ref[...]
    for c in range(x.shape[1] // LANES):
        acc += sq[:, c * LANES:(c + 1) * LANES]
    acc_ref[...] = acc

    @pl.when(fi == pl.num_programs(1) - 1)
    def _write():
        o_ref[...] = jnp.sum(acc_ref[...].T, axis=0, keepdims=True)


def rownorm2(x: jax.Array, block_rows: int = DEFAULT_BLOCK_ROWS,
             block_feat: int = DEFAULT_BLOCK_FEAT,
             interpret: bool | None = None) -> jax.Array:
    """sum(x^2, axis=-1) for x: (N, F) -> (N,) float32."""
    return _rownorm2(x, block_rows=block_rows, block_feat=block_feat,
                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_rows", "block_feat",
                                             "interpret"))
def _rownorm2(x: jax.Array, block_rows: int, block_feat: int,
              interpret: bool) -> jax.Array:
    N, F = x.shape
    br = min(block_rows, _round_up(N, LANES))
    bf = min(block_feat, _round_up(F, LANES))
    nr, nf = -(-N // br), -(-F // bf)
    # zero padding adds nothing to a sum of squares
    xp = jnp.pad(x, ((0, nr * br - N), (0, nf * bf - F)))
    out = pl.pallas_call(
        _rownorm2_kernel,
        grid=(nr, nf),
        in_specs=[pl.BlockSpec((br, bf), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, br), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nr * br), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, LANES), jnp.float32)],
        interpret=interpret,
    )(xp)
    return out[0, :N]


def gradnorm_sigma(h: jax.Array, dlogits: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """sigma = (||h||^2 + 1) * ||dlogits||^2 per row."""
    return (rownorm2(h, interpret=interpret) + 1.0) \
        * rownorm2(dlogits, interpret=interpret)
