"""Exact MXU executor for a CMVM step whose adder graph is a plain matmul.

The FPGA builds ``y = x @ M`` from shift-adds because it has no spare
multipliers; the TPU does.  When every shift of a solved program is a
left shift, the program is linear, so it equals ``x @ M`` for the
integer matrix ``M`` it computes on the identity.  One bfloat16 dot with
float32 accumulation then returns the program's int32 answers bit for
bit, provided that

* every input value is an integer bfloat16 holds exactly (|x| <= 256,
  bfloat16 has 8 significant bits),
* every entry of ``M`` is exact in bfloat16, and
* for every output column ``sum_i max|x_i| * |M_ij| < 2**24``: each
  product and each partial sum, in any order, is then an integer that
  float32 holds exactly.

:func:`dot_matrix` proves those conditions from the step's own tables
and the input intervals of its DAIS program, and returns ``M`` only
then; :func:`cmvm_dot` runs it, and :func:`cmvm_conv` runs it over every
window of a VALID convolution, whose im2col columns are the step's
inputs: each output is the same column sum, so the same proof covers
it.  ``M`` is read out of the solved adder
graph, not out of the float weights, so the dot serves the program's own
function.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from .ops import AdderGraphTables

BF16_EXACT_INT = 256  # every integer of magnitude up to this is exact in bfloat16
F32_EXACT_INT = 1 << 24  # every integer of smaller magnitude is exact in float32


@functools.lru_cache(maxsize=64)
def program_matrix(tables: AdderGraphTables) -> np.ndarray | None:
    """``M`` (int64 ``[n_in, n_out]``, read-only) with ``y = x @ M`` for
    every integer ``x``, read off the tables on the identity exactly as
    ``adder_graph_ref`` executes them; None when a shift is negative (an
    output right shift floors, so the program is not linear).  Cached by
    the tables' digest."""
    instr, outs = np.asarray(tables.instr), np.asarray(tables.outs)
    if (instr[:, 2:4] < 0).any() or (outs[:, 1] < 0).any():
        return None
    n = tables.n_inputs
    v = np.zeros((n + tables.n_ops, n), np.int64)  # row r: coefficients of row r
    v[:n] = np.eye(n, dtype=np.int64)
    a, b, sh_a, sh_b, sign = (instr[:, k].astype(np.int64)[:, None] for k in range(5))
    for lo, hi in tables.level_bounds:
        v[n + lo : n + hi] = (v[a[lo:hi, 0]] << sh_a[lo:hi]) + sign[lo:hi] * (
            v[b[lo:hi, 0]] << sh_b[lo:hi]
        )
    row, shift, osign, mask = (outs[:, k].astype(np.int64) for k in range(4))
    m = (v[row] << shift[:, None]).T * (osign * mask)
    m.setflags(write=False)
    return m


def dot_matrix(tables: AdderGraphTables, program: dict | None) -> np.ndarray | None:
    """``M`` when one bfloat16 x bfloat16 -> float32 dot computes the step
    exactly (module docstring), else None.

    ``program`` is the step's packed DAIS program
    (``DAISProgram.to_arrays``); its input rows give each input's
    interval.  None (a program that could not be packed) keeps the adder
    graph."""
    if program is None:
        return None
    m = program_matrix(tables)
    if m is None:
        return None
    rows = np.asarray(program["rows"], np.int64)[: tables.n_inputs]  # inputs come first
    x_max = np.maximum(np.abs(rows[:, 8]), np.abs(rows[:, 9]))
    if (x_max > BF16_EXACT_INT).any():
        return None
    if not np.array_equal(m.astype(jnp.bfloat16).astype(np.float64), m.astype(np.float64)):
        return None
    # float64 decides the bound exactly: below 2**24 every term and sum is
    # exact, and rounding cannot carry a larger sum below 2**24
    if (x_max.astype(np.float64) @ np.abs(m).astype(np.float64) >= F32_EXACT_INT).any():
        return None
    return m


def cmvm_dot(m: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``x @ m`` on the MXU: x int ``[..., n_in]``, m bfloat16
    ``[n_in, n_out]`` from :func:`dot_matrix`; returns int32
    ``[..., n_out]``."""
    y = lax.dot_general(
        x.astype(jnp.bfloat16), m,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y.astype(jnp.int32)


def cmvm_conv(m: jnp.ndarray, x: jnp.ndarray, strides: tuple[int, int]) -> jnp.ndarray:
    """The VALID convolution of x int ``[B, h, w, cin]`` with m bfloat16
    ``[kh, kw, cin, n_out]``, the :func:`dot_matrix` of the step's im2col
    columns in their (dy, dx, c) order, channel minor; returns int32
    ``[B, oh, ow, n_out]``, each window's ``patch @ M``."""
    y = lax.conv_general_dilated(
        x.astype(jnp.bfloat16), m, strides, "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
    return y.astype(jnp.int32)
