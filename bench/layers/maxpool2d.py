"""``maxpool2d``: the maximum of each non-overlapping ``size`` [ph, pw]
window of an event [h, w, c] (the program's ``MaxPool2D``).  The output
is [h // ph, w // pw, c], a remainder of rows or columns dropped; its
values stay on the input's grid.  No weights, no ops.
"""

PROGRAM = "MaxPool2D"


def describe(spec, seq):
    return {"size": [int(s) for s in spec.size]}


def init(layer, shape, rng, wcfg, seq):
    return {}, work(layer, shape, seq)[2]


def forward(x, p, layer, cur, precision, seq):
    (ph, pw), (n, h, w, c) = layer["size"], x.shape
    oh, ow = h // ph, w // pw
    win = x[:, : oh * ph, : ow * pw, :].reshape(n, oh, ph, ow, pw, c)
    return win.max(axis=(2, 4)), cur


def work(layer, shape, seq):
    (ph, pw), (h, w, c) = layer["size"], shape
    return 0, 0, (h // ph, w // pw, c)
