"""``dense``: a quantized fully connected layer over the last axis.

Keys: ``units``, ``w_quant``, and ``out_quant`` where the program's
``QDense`` requantizes its own output (floor, saturate).  Weights
``[d_in, units]`` are rounded to the nearest point of ``w_quant``,
biases onto the accumulator grid.
"""

import numpy as np

from bench.harness.network import glorot, quant_dict
from bench.harness.reference import affine, requantize
from bench.harness.work import weight_bytes

PROGRAM = "QDense"


def describe(spec, seq):
    d = {"units": int(spec.units), "w_quant": quant_dict(spec.w_quant)}
    if spec.out_quant is not None:
        d["out_quant"] = quant_dict(spec.out_quant)
    return d


def init(layer, shape, rng, wcfg, seq):
    units = layer["units"]
    return glorot(rng, wcfg, (shape[-1], units)), (*shape[:-1], units)


def forward(x, p, layer, cur, precision, seq):
    y = affine(x, p, layer["w_quant"], cur, precision)
    return requantize(y, layer)


def work(layer, shape, seq):
    d_in, units = shape[-1], layer["units"]
    macs = int(np.prod(shape[:-1])) * d_in * units
    return macs, weight_bytes(layer["w_quant"], d_in * units, units), (*shape[:-1], units)
