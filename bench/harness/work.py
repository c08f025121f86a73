"""Operations and bytes of a configuration's network, from its layer shapes.

They count the network's work, the same whatever implements it: an
operation is one multiply or one add of a multiply-accumulate, so ops
are 2 x the MACs.  Each layer's count is its kind's ``work`` under
``bench/layers/`` (``kinds.py``): dense, dense-on-axis and convolution
layers count their multiply-accumulates (a convolution's are
oh·ow·kh·kw·cin·filters an event); pooling, ReLU, flattening and the
residual sum count no ops.  Bytes of a call are the input events at the
dtype sent, the int32 outputs, and the weights once (int8, since no
weight grid is wider than 8 bits) with their int32 biases.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from . import kinds
from .cell import ROOT


def weight_bytes(w_quant: dict, n_weights: int, n_biases: int) -> int:
    """Bytes of a layer's weights as int8 and its biases as int32."""
    if w_quant["bits"] > 8:
        raise ValueError("weights wider than 8 bits are not counted as int8")
    return n_weights + 4 * n_biases


def _walk(layers: list, shape: tuple, root: Path) -> tuple[int, int, tuple]:
    """(MACs, weight bytes, output shape) of one event through ``layers``."""

    def seq(body, shape):
        return _walk(body, shape, root)

    macs = wbytes = 0
    for layer in layers:
        m, b, shape = kinds.kind(layer["kind"], root).work(layer, shape, seq)
        macs, wbytes = macs + m, wbytes + b
    return macs, wbytes, shape


def macs_per_event(config: dict, root: Path = ROOT) -> int:
    return _walk(config["layers"], tuple(config["in_shape"]), root)[0]


def ops_per_event(config: dict, root: Path = ROOT) -> int:
    return 2 * macs_per_event(config, root)


def bytes_per_call(config: dict, events: int, in_itemsize: int, root: Path = ROOT) -> int:
    """Bytes one call of ``events`` events moves: inputs, outputs, weights."""
    _, wbytes, out = _walk(config["layers"], tuple(config["in_shape"]), root)
    n_in = int(np.prod(config["in_shape"]))
    return events * (n_in * in_itemsize + int(np.prod(out)) * 4) + wbytes


def least_time_s(config: dict, events: int, in_itemsize: int, peak: dict,
                 root: Path = ROOT) -> tuple[float, str]:
    """(seconds, bound): the least time the chip could take for one call,
    the larger of ops over the int8 peak and bytes over HBM bandwidth."""
    t_ops = ops_per_event(config, root) * events / peak["int8_ops_per_s"]
    t_mem = bytes_per_call(config, events, in_itemsize, root) / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
