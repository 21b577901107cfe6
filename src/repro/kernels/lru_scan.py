"""Blocked linear-recurrence scan kernel:  h_t = a_t * h_{t-1} + b_t.

Serves both recurrent mixers of the zoo (RG-LRU gates and the
diagonalized Mamba-1 recurrence, with the (d_inner, n_state) plane
flattened into channels).

Schedule: grid (batch, n_channel_blocks, n_seq_blocks) with the
sequence axis minor-most.  The carry h lives in VMEM scratch and
persists across the sequence sweep of each (batch, channel) block —
the cross-block dependency is the grid-carry, and inside a block the
recurrence runs as an unrolled-by-the-compiler fori over the (seq,
channel) VMEM tile.  One HBM read of a/b and one write of h per
element; VPU-only.

(The pure-jnp path uses jax.lax.associative_scan — log-depth but ~3x
the HBM traffic; this kernel is the linear-work alternative for real
TPUs.  Both validated against kernels/ref.py.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

DEFAULT_BLOCK_SEQ = 256
DEFAULT_BLOCK_CH = 256


def _scan_kernel(a_ref, b_ref, o_ref, h_ref, *, block_seq: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)  # (block_seq, block_ch)
    b = b_ref[0].astype(jnp.float32)

    def body(t, carry):
        h, out = carry
        h = a[t] * h + b[t]
        out = jax.lax.dynamic_update_index_in_dim(out, h, t, 0)
        return h, out

    h0 = h_ref[...]
    out0 = jnp.zeros_like(a)
    h_fin, out = jax.lax.fori_loop(0, block_seq, body, (h0, out0))
    h_ref[...] = h_fin
    o_ref[0] = out.astype(o_ref.dtype)


def lru_scan(a: jax.Array, b: jax.Array,
             block_seq: int = DEFAULT_BLOCK_SEQ,
             block_ch: int = DEFAULT_BLOCK_CH,
             interpret: bool | None = None) -> jax.Array:
    """a, b: (B, S, C) -> h: (B, S, C) with h_t = a_t h_{t-1} + b_t."""
    return _lru_scan(a, b, block_seq=block_seq, block_ch=block_ch,
                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_seq", "block_ch",
                                             "interpret"))
def _lru_scan(a: jax.Array, b: jax.Array, block_seq: int, block_ch: int,
              interpret: bool) -> jax.Array:
    B, S, C = a.shape
    bs = min(block_seq, S)
    bc = min(block_ch, max(128, C))
    ns, nc = -(-S // bs), -(-C // bc)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, ns * bs - S), (0, nc * bc - C)))

    out = pl.pallas_call(
        functools.partial(_scan_kernel, block_seq=bs),
        grid=(B, nc, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bc), lambda b, c, s: (b, s, c)),
            pl.BlockSpec((1, bs, bc), lambda b, c, s: (b, s, c)),
        ],
        out_specs=pl.BlockSpec((1, bs, bc), lambda b, c, s: (b, s, c)),
        out_shape=jax.ShapeDtypeStruct((B, ns * bs, nc * bc), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bc,), jnp.float32)],
        interpret=interpret,
    )(pad(a), pad(b))
    return out[:, :S, :C]
