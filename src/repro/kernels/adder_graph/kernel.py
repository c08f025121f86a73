"""Pallas TPU kernel: levelized adder-graph execution over batch tiles.

TPU adaptation of the paper's FPGA adder tree (DESIGN.md §Hardware
adaptation): instead of spatial unrolling onto LUTs, the DAIS program is
levelized (ops grouped by adder depth, operands always in earlier rows)
and executed as VPU-parallel gathers + shifts + adds over a batch tile
held in VMEM:

    V[level_k rows] = (V[a] << sh_a) + sign * (V[b] << sh_b)

The instruction table is a real kernel input (Pallas forbids captured
array constants); level boundaries are static, so XLA sees one gather +
shift + add per level, vectorised across that level's ops and across the
batch tile.

BlockSpec tiling: the batch dimension is tiled to ``block_b`` lanes; the
value buffer for one tile ([n_rows, block_b] int32) lives in VMEM.  For a
typical quantized NN layer (n_rows ~ 4k, block_b = 256) that is ~4 MB —
comfortably inside the ~16 MB VMEM of a TPU core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _adder_graph_kernel(x_ref, instr_ref, outs_ref, o_ref, *, level_bounds):
    v = x_ref[...].T.astype(jnp.int32)  # [n_inputs, block_b]
    for lo, hi in level_bounds:
        ops = instr_ref[lo:hi]  # static slice: [n_level, 5]
        a = jnp.take(v, ops[:, 0], axis=0) << ops[:, 2][:, None]
        b = jnp.take(v, ops[:, 1], axis=0) << ops[:, 3][:, None]
        v = jnp.concatenate([v, a + ops[:, 4][:, None] * b], axis=0)
    outs = outs_ref[...]
    y = jnp.take(v, outs[:, 0], axis=0)
    shift = outs[:, 1][:, None]
    y = jnp.where(shift >= 0, y << jnp.maximum(shift, 0), y >> jnp.maximum(-shift, 0))
    o_ref[...] = (y * outs[:, 2][:, None] * outs[:, 3][:, None]).T


@functools.partial(jax.jit, static_argnames=("tables", "block_b", "interpret"))
def adder_graph_pallas(tables, x: jnp.ndarray, block_b: int = 256, interpret: bool | None = None):
    """Run the adder graph on int32 inputs [batch, n_in] via pallas_call.

    ``interpret=None`` interprets the kernel body on the CPU backend
    (bit-exact) and compiles it elsewhere.  It does not lower through
    Mosaic yet (the per-level ``jnp.take`` gathers are refused), so
    ``build_steps`` refuses ``use_pallas=True`` off the CPU.
    """
    batch, n_in = x.shape
    n_out = tables.n_outputs
    n_ops = max(tables.n_ops, 1)
    instr = jnp.asarray(tables.instr) if tables.n_ops else jnp.zeros((1, 5), jnp.int32)
    outs = jnp.asarray(tables.outs)
    pad = (-batch) % block_b
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    padded = batch + pad
    grid = (padded // block_b,)
    out = pl.pallas_call(
        functools.partial(_adder_graph_kernel, level_bounds=tables.level_bounds),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n_in), lambda i: (i, 0)),
            pl.BlockSpec((n_ops, 5), lambda i: (0, 0)),
            pl.BlockSpec((n_out, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, n_out), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x.astype(jnp.int32), instr, outs)
    return out[:batch]
