"""Operations and bytes of a configuration's network, from its layer shapes.

They count the network's work, the same whatever implements it: an
operation is one multiply or one add of a multiply-accumulate, so ops
are 2 x the MACs of every dense and dense-on-axis layer.  Bytes of a
call are the input events at the dtype sent, the int32 outputs, and the
weights once (int8, since no weight grid is wider than 8 bits) with
their int32 biases.
"""

from __future__ import annotations

import numpy as np


def _walk(layers: list, shape: tuple) -> tuple[int, int, tuple]:
    """(MACs, weight bytes, output shape) of one event through ``layers``."""
    macs = wbytes = 0
    for layer in layers:
        kind = layer["kind"]
        if kind in ("dense", "dense_on_axis"):
            if layer["w_quant"]["bits"] > 8:
                raise ValueError("weights wider than 8 bits are not counted as int8")
            ax = layer["axis"] if kind == "dense_on_axis" else len(shape) - 1
            d_in, units = shape[ax], layer["units"]
            macs += int(np.prod(shape)) // d_in * d_in * units
            wbytes += d_in * units + 4 * units
            shape = tuple(units if i == ax else s for i, s in enumerate(shape))
        elif kind == "flatten":
            shape = (int(np.prod(shape)),)
        elif kind == "residual":
            m, b, _ = _walk(layer["body"], shape)
            macs, wbytes = macs + m, wbytes + b
        elif kind != "relu":
            raise ValueError(f"no work count for layer kind {kind!r}")
    return macs, wbytes, shape


def macs_per_event(config: dict) -> int:
    return _walk(config["layers"], tuple(config["in_shape"]))[0]


def ops_per_event(config: dict) -> int:
    return 2 * macs_per_event(config)


def bytes_per_call(config: dict, events: int, in_itemsize: int) -> int:
    """Bytes one call of ``events`` events moves: inputs, outputs, weights."""
    _, wbytes, out = _walk(config["layers"], tuple(config["in_shape"]))
    n_in = int(np.prod(config["in_shape"]))
    return events * (n_in * in_itemsize + int(np.prod(out)) * 4) + wbytes


def least_time_s(config: dict, events: int, in_itemsize: int, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the least time the chip could take for one call,
    the larger of ops over the int8 peak and bytes over HBM bandwidth."""
    t_ops = ops_per_event(config) * events / peak["int8_ops_per_s"]
    t_mem = bytes_per_call(config, events, in_itemsize) / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
