"""Pallas TPU kernel: fused Mamba-1 selective scan.

The production answer to the SSM memory floor measured in EXPERIMENTS.md
§Perf cell 2: the recurrent state h [tile_d, N] lives in VMEM for the
whole sequence, so HBM traffic is exactly the input/output streams
(dt, B, C, x in; y out) — the [B, S, D, N] state tensor never exists,
matching the hand-derived optimum the time-major jnp scan approximates.

Grid: (B, D/tile_d); each program instance scans its channel tile over
the full sequence with a fori_loop, carrying h in registers/VMEM.
Sequence blocks of the inputs are resident per instance (choose tile_d
so (4 streams x S x tile_d x 4B) fits VMEM; e.g. S=4096, tile_d=128 ->
~8.5 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _ssm_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, h0_ref, y_ref, hout_ref, *, seq_len):
    a = a_ref[...]  # [tile_d, N]
    h = h0_ref[0]  # [tile_d, N]

    def body(t, h):
        dt_t = dt_ref[0, t, :]  # [tile_d]
        decay = jnp.exp(dt_t[:, None] * a)
        bx = dt_t[:, None] * b_ref[0, t, :][None, :] * x_ref[0, t, :][:, None]
        h = decay * h + bx
        y_ref[0, t, :] = jnp.sum(h * c_ref[0, t, :][None, :], axis=-1)
        return h

    h = jax.lax.fori_loop(0, seq_len, body, h)
    hout_ref[0] = h


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret"))
def selective_scan_pallas(
    dt: jnp.ndarray,  # f32 [B, S, D]
    bmat: jnp.ndarray,  # f32 [B, S, N]
    cmat: jnp.ndarray,  # f32 [B, S, N]
    x: jnp.ndarray,  # f32 [B, S, D]
    a: jnp.ndarray,  # f32 [D, N]
    h0: jnp.ndarray,  # f32 [B, D, N]
    tile_d: int = 128,
    interpret: bool | None = None,
):
    b, s, d = dt.shape
    n = a.shape[1]
    tile_d = min(tile_d, d)
    if d % tile_d:
        raise ValueError("d_inner must divide tile_d")
    grid = (b, d // tile_d)
    kernel = functools.partial(_ssm_kernel, seq_len=s)
    y, h_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, tile_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, s, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, tile_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((tile_d, n), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tile_d, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, tile_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, tile_d, n), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d, n), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(dt, bmat, cmat, x, a, h0)
    return y, h_out
