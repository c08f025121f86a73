"""``avgpool2d``: the mean of each non-overlapping ``size`` [ph, pw]
window of an event [h, w, c] (the program's ``AvgPool2D``).  The window
holds a power of two of values, so the mean is their sum shifted by
log2(ph·pw), which is exact: the grid's step falls by that factor, and
its range holds the sums.  The output is [h // ph, w // pw, c], a
remainder of rows or columns dropped.  No weights, no ops.
"""

PROGRAM = "AvgPool2D"


def _shift(layer):
    k = layer["size"][0] * layer["size"][1]
    if k & (k - 1):
        raise ValueError(f"avgpool2d window of {k} values: only a power of two is exact")
    return k.bit_length() - 1


def describe(spec, seq):
    return {"size": [int(s) for s in spec.size]}


def init(layer, shape, rng, wcfg, seq):
    return {}, work(layer, shape, seq)[2]


def forward(x, p, layer, cur, precision, seq):
    (ph, pw), (n, h, w, c) = layer["size"], x.shape
    oh, ow, s = h // ph, w // pw, _shift(layer)
    win = x[:, : oh * ph, : ow * pw, :].reshape(n, oh, ph, ow, pw, c)
    y = win.sum(axis=(2, 4)) * 2.0**-s
    if cur is not None:
        cur = {**cur, "bits": cur["bits"] + s}
    return y, cur


def work(layer, shape, seq):
    (ph, pw), (h, w, c) = layer["size"], shape
    _shift(layer)
    return 0, 0, (h // ph, w // pw, c)
