"""``dense_on_axis``: a quantized fully connected layer over one axis of
the event other than the last (the program's ``QDenseOnAxis``, the
MLP-Mixer's token mix).

Keys: ``axis`` (of the event, without the batch), ``units``, ``w_quant``,
and ``out_quant`` where the layer requantizes its own output.  Numerics
as ``dense``, with the axis moved last and back.
"""

import numpy as np

from bench.harness.network import glorot, quant_dict
from bench.harness.reference import affine, requantize
from bench.harness.work import weight_bytes

PROGRAM = "QDenseOnAxis"


def _out(shape, ax, units):
    return tuple(units if i == ax else s for i, s in enumerate(shape))


def describe(spec, seq):
    d = {"axis": int(spec.axis), "units": int(spec.units), "w_quant": quant_dict(spec.w_quant)}
    if spec.out_quant is not None:
        d["out_quant"] = quant_dict(spec.out_quant)
    return d


def init(layer, shape, rng, wcfg, seq):
    ax, units = layer["axis"], layer["units"]
    return glorot(rng, wcfg, (shape[ax], units)), _out(shape, ax, units)


def forward(x, p, layer, cur, precision, seq):
    ax = layer["axis"] + 1  # the block's first axis is the event
    y = np.moveaxis(affine(np.moveaxis(x, ax, -1), p, layer["w_quant"], cur, precision), -1, ax)
    return requantize(y, layer)


def work(layer, shape, seq):
    ax, units = layer["axis"], layer["units"]
    d_in = shape[ax]
    macs = int(np.prod(shape)) // d_in * d_in * units
    return macs, weight_bytes(layer["w_quant"], d_in * units, units), _out(shape, ax, units)
