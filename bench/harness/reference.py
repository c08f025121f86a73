"""Plain numpy reference of a configuration's network.

It reads the network from the configuration file (``layers``) and the
float weights the benchmark drew for it, and computes the real-valued
output of the fixed-point network those describe.  Each layer's
semantics is its kind's ``forward`` under ``bench/layers/``
(``kinds.py``); the numerics they share are here: a fixed-point grid,
rounding onto it, the matrix product in each precision, and the affine
map whose weights are rounded to the nearest point of their grid and
whose biases are rounded onto the accumulator grid (input step times
weight step, 24 bits).  These are the semantics of HGQ-style quantized
networks that da4ml compiles.  It imports nothing of the program under
test.

``precision="float64"`` is exact here: every value is a dyadic
rational far inside float64's 53-bit significand.  The lower precisions
exist for the control of the correctness check (see PERF.md): in
``"float32"`` the matrix products run in float32, in ``"bfloat16"``
their operands and results are rounded to bfloat16 with float32
accumulation, as a matrix unit computing in bfloat16 would.
"""

from __future__ import annotations

from pathlib import Path

import ml_dtypes
import numpy as np

from . import kinds
from .cell import ROOT

PRECISIONS = ("float64", "float32", "bfloat16")
BIAS_BITS = 24
BLOCK_EVENTS = 4096


def grid(q: dict) -> tuple[int, int, int]:
    """(lo, hi, exp) of a fixed<signed, bits, int_bits> grid, in steps of 2**exp."""
    bits, int_bits, signed = int(q["bits"]), int(q["int_bits"]), bool(q["signed"])
    mag = bits - 1 if signed else bits
    lo = -(1 << mag) if signed else 0
    return lo, (1 << mag) - 1, int_bits - bits


def on_grid(x: np.ndarray, q: dict, rounding: str) -> np.ndarray:
    """``x`` rounded (``"floor"`` or ``"round"``) onto grid ``q``, saturated."""
    lo, hi, exp = grid(q)
    step = 2.0**exp
    k = np.floor(x / step) if rounding == "floor" else np.round(x / step)
    return np.clip(k, lo, hi) * step


def matmul(x: np.ndarray, w: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return x @ w
    if precision == "float32":
        return (x.astype(np.float32) @ w.astype(np.float32)).astype(np.float64)
    bf = ml_dtypes.bfloat16
    xb = x.astype(bf).astype(np.float32)
    wb = w.astype(bf).astype(np.float32)
    return (xb @ wb).astype(bf).astype(np.float64)


def affine(x, p: dict, w_quant: dict, cur: dict | None, precision: str) -> np.ndarray:
    """``x @ w + b`` over the last axis of ``x``, with ``w`` [d_in, units]
    rounded onto ``w_quant`` and ``b`` onto the accumulator grid of the
    input grid ``cur``."""
    w = on_grid(np.asarray(p["w"], np.float64), w_quant, "round")
    y = matmul(x, w, precision)
    if "b" in p:
        if cur is None:
            raise ValueError("a biased layer needs its input on a known grid")
        exp = grid(w_quant)[2] + grid(cur)[2]
        bias_q = {"bits": BIAS_BITS, "int_bits": BIAS_BITS + exp, "signed": True}
        y = y + on_grid(np.asarray(p["b"], np.float64), bias_q, "round")
    return y


def requantize(y: np.ndarray, layer: dict) -> tuple[np.ndarray, dict | None]:
    """A weighted layer's outputs and their grid: floored and saturated
    onto its ``out_quant`` where it has one, else on no single grid."""
    if "out_quant" in layer:
        return on_grid(y, layer["out_quant"], "floor"), layer["out_quant"]
    return y, None


def _run(layers, params, x, cur, precision, root):
    def seq(body, body_params, x, cur):
        return _run(body, body_params, x, cur, precision, root)

    for layer, p in zip(layers, params):
        x, cur = kinds.kind(layer["kind"], root).forward(x, p, layer, cur, precision, seq)
    return x, cur


def forward(config: dict, params: list, x_int: np.ndarray, precision: str = "float64",
            root: Path = ROOT):
    """Real-valued outputs [n, *out_shape] (float64) for integer events
    ``x_int`` [n, *in_shape] on the configuration's input grid, computed
    in blocks of ``BLOCK_EVENTS`` events."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    step = 2.0 ** grid(config["in_quant"])[2]
    out = []
    for i in range(0, len(x_int), BLOCK_EVENTS):
        x = np.asarray(x_int[i : i + BLOCK_EVENTS], np.float64) * step
        out.append(_run(config["layers"], params, x, config["in_quant"], precision, root)[0])
    return np.concatenate(out)
