"""``conv2d``: a quantized 2-D convolution over an event [h, w, cin]
(the program's ``QConv2D``), as the compiler lowers it: an im2col of
kh·kw strided slices, then one affine map of the kh·kw·cin columns.

Keys: ``filters``, ``kernel`` [kh, kw], ``strides`` [sh, sw], ``padding``
(``VALID`` only: the compiler refuses any other, and so does this file),
``w_quant``, and ``out_quant`` where the layer requantizes its own
output.  Weights [kh, kw, cin, filters] are Glorot-uniform with fan-in
kh·kw·cin, then one uniform bias per filter.  Work: oh·ow·kh·kw·cin·filters
MACs an event.
"""

import numpy as np

from bench.harness.network import glorot, quant_dict
from bench.harness.reference import affine, requantize
from bench.harness.work import weight_bytes

PROGRAM = "QConv2D"


def _out(layer, shape):
    if layer["padding"] != "VALID":
        raise ValueError(f"conv2d padding {layer['padding']!r}: only VALID is compiled")
    (h, w, _), (kh, kw), (sh, sw) = shape, layer["kernel"], layer["strides"]
    return (h - kh) // sh + 1, (w - kw) // sw + 1, layer["filters"]


def describe(spec, seq):
    d = {
        "filters": int(spec.filters),
        "kernel": [int(k) for k in spec.kernel],
        "strides": [int(s) for s in spec.strides],
        "padding": spec.padding,
        "w_quant": quant_dict(spec.w_quant),
    }
    if spec.out_quant is not None:
        d["out_quant"] = quant_dict(spec.out_quant)
    return d


def init(layer, shape, rng, wcfg, seq):
    out = _out(layer, shape)
    kh, kw = layer["kernel"]
    return glorot(rng, wcfg, (kh, kw, shape[-1], layer["filters"])), out


def forward(x, p, layer, cur, precision, seq):
    oh, ow, filters = _out(layer, x.shape[1:])
    (kh, kw), (sh, sw) = layer["kernel"], layer["strides"]
    cols = np.concatenate(
        [
            x[:, dy : dy + sh * (oh - 1) + 1 : sh, dx : dx + sw * (ow - 1) + 1 : sw, :]
            for dy in range(kh)
            for dx in range(kw)
        ],
        axis=-1,
    )  # [n, oh, ow, kh*kw*cin], in the order of the weights' first three axes
    wmat = {**p, "w": np.reshape(p["w"], (-1, filters))}
    y = affine(cols, wmat, layer["w_quant"], cur, precision)
    return requantize(y, layer)


def work(layer, shape, seq):
    oh, ow, filters = _out(layer, shape)
    kh, kw = layer["kernel"]
    k = kh * kw * shape[-1]
    return oh * ow * k * filters, weight_bytes(layer["w_quant"], k * filters, filters), (oh, ow, filters)
