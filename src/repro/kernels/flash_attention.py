"""Causal flash attention as a Pallas TPU kernel.

Schedule: grid (batch*heads, n_q_blocks, n_k_blocks) with the k axis
minor-most, so the online-softmax accumulators (m, l, acc) live in VMEM
scratch and persist across the k sweep of each q block — the classic
flash schedule mapped to the TPU grid-carry idiom (no atomics, no
shared-memory tiles; the MXU consumes (block_q x d) @ (d x block_k)
tiles directly from VMEM).

Block sizes default to (128, 128): multiples of the (8, 128) VPU lanes
and the 128x128 MXU, and small enough that q/k/v/acc tiles fit VMEM
(~(2*128*d + 128*d + 128*128) * 4B << 16 MiB for d <= 256).

Fully-masked k blocks (block start beyond the causal diagonal) are
skipped with pl.when, so the causal sweep does ~half the work — this is
the optimization the paper-agnostic roofline pass credits attention
with (HLO cost_analysis of the jnp path counts the full rectangle).

Validated in interpret mode against kernels/ref.py on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, seq: int,
                  causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # last k block this q block needs (causal) — also the write step
    last_ki = jnp.minimum((q_start + block_q - 1) // block_k, nk - 1) \
        if causal else nk - 1

    @pl.when((k_start <= q_start + block_q - 1) | (not causal))
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = cols < seq  # key padding
        if causal:
            mask &= rows >= cols
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == last_ki)
    def _write():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None) -> jax.Array:
    """q, k, v: (BH, S, d) — batch and heads pre-merged, MHA layout.

    Sequences are padded to the block size internally; ``interpret=None``
    compiles on a TPU backend and interprets elsewhere.
    """
    return _flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def _flash_attention(q, k, v, causal: bool, scale: float | None,
                     block_q: int, block_k: int,
                     interpret: bool) -> jax.Array:
    BH, S, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    nq = -(-S // block_q)
    nk = -(-S // block_k)
    Sp_q, Sp_k = nq * block_q, nk * block_k

    def padk(x, to):
        return jnp.pad(x, ((0, 0), (0, to - S), (0, 0)))

    qp, kp, vp = padk(q, Sp_q), padk(k, Sp_k), padk(v, Sp_k)
    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, seq=S, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),    # running max m
            pltpu.VMEM((block_q,), jnp.float32),    # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :S]
