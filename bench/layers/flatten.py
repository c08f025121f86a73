"""``flatten``: an event's values in one axis, in row-major order (the
program's ``Flatten``).  Exact; no weights, no ops.
"""

import numpy as np

PROGRAM = "Flatten"


def describe(spec, seq):
    return {}


def init(layer, shape, rng, wcfg, seq):
    return {}, (int(np.prod(shape)),)


def forward(x, p, layer, cur, precision, seq):
    return x.reshape(x.shape[0], -1), cur


def work(layer, shape, seq):
    return 0, 0, (int(np.prod(shape)),)
