"""``residual``: x + body(x), the body a list of layers that keeps the
event's shape (the program's ``Residual``, the MLP-Mixer's skip).

Key: ``body``.  The sum is exact; its values lie on no single grid the
next biased layer could take, so that layer needs a requantization
first.  Its work is the body's; the sum counts no ops.
"""

PROGRAM = "Residual"


def describe(spec, seq):
    return {"body": seq(spec.body)}


def init(layer, shape, rng, wcfg, seq):
    body, _ = seq(layer["body"], shape)
    return {"body": body}, shape


def forward(x, p, layer, cur, precision, seq):
    y, _ = seq(layer["body"], p["body"], x, cur)
    return x + y, None


def work(layer, shape, seq):
    macs, wbytes, _ = seq(layer["body"], shape)
    return macs, wbytes, shape
