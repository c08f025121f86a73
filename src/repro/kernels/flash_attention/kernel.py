"""Pallas TPU flash-attention kernel (GQA, causal/full), online softmax.

Grid: (batch, q_heads, q_blocks).  Each program instance streams the KV
sequence for its (b, h) pair in ``block_k`` tiles held in VMEM, keeping
the FlashAttention running max / normaliser / accumulator in registers.
MXU-aligned block shapes (multiples of 128 on the contracting dims) are
chosen by the wrapper in ops.py.

Causal masking uses an absolute query offset (``offset`` = position of
the first query token), so the same kernel serves training (offset 0),
prefill into a preallocated cache (offset 0, Sk = cache size) and decode
(Sq = 1, offset = current position).  KV blocks entirely above the
causal frontier are skipped via the loop bound, so causal prefill does
~half the work and decode touches only the live prefix of the cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, off_ref, o_ref, *, scale, causal, block_k, sk):
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, d]
    bq, d = q.shape
    nk = sk // block_k
    q_block = pl.program_id(2)
    offset = off_ref[0]
    q_pos = q_block * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0) + offset

    if causal:
        # last kv block intersecting this q block's causal window
        hi = (q_block + 1) * bq + offset  # exclusive max key pos
        nk_eff = jnp.minimum((hi + block_k - 1) // block_k, nk)
    else:
        nk_eff = nk

    def body(ik, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, 0, pl.dslice(ik * block_k, block_k), :]
        v = v_ref[0, 0, pl.dslice(ik * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T  # [bq, block_k]
        if causal:
            k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + p @ v.astype(jnp.float32)
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, _, lsum = jax.lax.fori_loop(0, nk_eff, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(lsum, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(
    q: jnp.ndarray,  # [B, Hq, Sq, D]
    k: jnp.ndarray,  # [B, Hkv, Sk, D]
    v: jnp.ndarray,
    offset: jnp.ndarray,  # scalar int32: absolute position of q[0]
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    scale = d**-0.5 if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("sequence lengths must divide block sizes")
    grid = (b, hq, sq // block_q)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_k=block_k, sk=sk
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda b_, h, iq, g=group: (b_, h // g, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda b_, h, iq, g=group: (b_, h // g, 0, 0)),
            pl.BlockSpec((1,), lambda b_, h, iq: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v, jnp.asarray(offset, jnp.int32).reshape(1))
