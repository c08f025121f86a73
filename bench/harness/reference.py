"""Plain numpy reference of a configuration's network.

It reads the network from the configuration file (``layers``) and the
float weights the benchmark drew for it, and computes the real-valued
output of the fixed-point network those describe: weights rounded to
the nearest point of their grid, biases rounded onto the accumulator
grid (input step times weight step, 24 bits), ReLU outputs floored and
saturated onto the activation grid, residual sums and flattening
exact.  These are the semantics of HGQ-style quantized networks that
da4ml compiles.  It imports nothing of the program under test.

``precision="float64"`` is exact here: every value is a dyadic
rational far inside float64's 53-bit significand.  The lower precisions
exist for the control of the correctness check (see PERF.md): in
``"float32"`` the matrix products run in float32, in ``"bfloat16"``
their operands and results are rounded to bfloat16 with float32
accumulation, as a matrix unit computing in bfloat16 would.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

PRECISIONS = ("float64", "float32", "bfloat16")
BIAS_BITS = 24
BLOCK_EVENTS = 4096


def grid(q: dict) -> tuple[int, int, int]:
    """(lo, hi, exp) of a fixed<signed, bits, int_bits> grid, in steps of 2**exp."""
    bits, int_bits, signed = int(q["bits"]), int(q["int_bits"]), bool(q["signed"])
    mag = bits - 1 if signed else bits
    lo = -(1 << mag) if signed else 0
    return lo, (1 << mag) - 1, int_bits - bits


def _on_grid(x: np.ndarray, q: dict, rounding: str) -> np.ndarray:
    lo, hi, exp = grid(q)
    step = 2.0**exp
    k = np.floor(x / step) if rounding == "floor" else np.round(x / step)
    return np.clip(k, lo, hi) * step


def _matmul(x: np.ndarray, w: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return x @ w
    if precision == "float32":
        return (x.astype(np.float32) @ w.astype(np.float32)).astype(np.float64)
    bf = ml_dtypes.bfloat16
    xb = x.astype(bf).astype(np.float32)
    wb = w.astype(bf).astype(np.float32)
    return (xb @ wb).astype(bf).astype(np.float64)


def _dense(x, p, layer, cur, precision, axis=None):
    w = _on_grid(np.asarray(p["w"], np.float64), layer["w_quant"], "round")
    if axis is not None:
        x = np.moveaxis(x, axis, -1)
    y = _matmul(x, w, precision)
    if "b" in p:
        if cur is None:
            raise ValueError("a biased layer needs its input on a known grid")
        exp = grid(layer["w_quant"])[2] + grid(cur)[2]
        bias_q = {"bits": BIAS_BITS, "int_bits": BIAS_BITS + exp, "signed": True}
        y = y + _on_grid(np.asarray(p["b"], np.float64), bias_q, "round")
    if axis is not None:
        y = np.moveaxis(y, -1, axis)
    return y


def _run(layers, params, x, cur, precision):
    for layer, p in zip(layers, params):
        kind = layer["kind"]
        if kind == "dense":
            x, cur = _dense(x, p, layer, cur, precision), None
        elif kind == "dense_on_axis":
            x, cur = _dense(x, p, layer, cur, precision, axis=layer["axis"] + 1), None
        elif kind == "relu":
            x = np.maximum(x, 0.0)
            if "out_quant" in layer:
                x, cur = _on_grid(x, layer["out_quant"], "floor"), layer["out_quant"]
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "residual":
            x, cur = x + _run(layer["body"], p["body"], x, cur, precision), None
        else:
            raise ValueError(f"reference has no layer kind {kind!r}")
    return x


def forward(config: dict, params: list, x_int: np.ndarray, precision: str = "float64"):
    """Real-valued outputs [n, *out_shape] (float64) for integer events
    ``x_int`` [n, *in_shape] on the configuration's input grid, computed
    in blocks of ``BLOCK_EVENTS`` events."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    step = 2.0 ** grid(config["in_quant"])[2]
    out = []
    for i in range(0, len(x_int), BLOCK_EVENTS):
        x = np.asarray(x_int[i : i + BLOCK_EVENTS], np.float64) * step
        out.append(_run(config["layers"], params, x, config["in_quant"], precision))
    return np.concatenate(out)
