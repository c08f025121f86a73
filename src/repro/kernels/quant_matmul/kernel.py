"""Pallas TPU kernel: int8 x int8 -> int32 matmul with per-row/-channel
dequantisation (W8A8).

This is the MXU-native realisation of a heavily-quantized CMVM — the
counterpart to the adder-graph executor in DESIGN.md §Hardware
adaptation: on LUT fabric the paper's shift-add graph wins; on a systolic
MXU an int8 matmul at 2x bf16 throughput is roofline-optimal.  The
framework exposes both per layer.

Grid: (M/bm, N/bn, K/bk) with K innermost (sequential), accumulating
into the revisited f32 output tile; dequant scales apply in the epilogue
at the final K step.  Block shapes default to MXU-aligned (128,128,256).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _qmm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, *, n_k):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == n_k - 1)
    def _epilogue():
        o_ref[...] = o_ref[...] * xs_ref[...][:, None] * ws_ref[...][None, :]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def quant_matmul_pallas(
    x: jnp.ndarray,  # int8 [M, K]
    w: jnp.ndarray,  # int8 [K, N]
    x_scale: jnp.ndarray,  # f32 [M]
    w_scale: jnp.ndarray,  # f32 [N]
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if m % bm or n % bn or k % bk:
        raise ValueError("dims must divide block sizes (pad upstream)")
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm,), lambda i, j, kk: (i,)),
            pl.BlockSpec((bn,), lambda i, j, kk: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, w, x_scale, w_scale)
