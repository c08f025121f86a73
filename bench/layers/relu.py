"""``relu``: max(x, 0), then, with ``out_quant``, floored and saturated
onto that activation grid (the program's ``ReLU``).  Without it the
values stay on the input's grid.  No weights, no ops.
"""

import numpy as np

from bench.harness.network import quant_dict
from bench.harness.reference import on_grid

PROGRAM = "ReLU"


def describe(spec, seq):
    return {} if spec.out_quant is None else {"out_quant": quant_dict(spec.out_quant)}


def init(layer, shape, rng, wcfg, seq):
    return {}, shape


def forward(x, p, layer, cur, precision, seq):
    x = np.maximum(x, 0.0)
    if "out_quant" in layer:
        return on_grid(x, layer["out_quant"], "floor"), layer["out_quant"]
    return x, cur


def work(layer, shape, seq):
    return 0, 0, shape
