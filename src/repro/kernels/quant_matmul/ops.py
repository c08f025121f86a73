"""Public W8A8 matmul op with padding + backend selection."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import quant_matmul_pallas
from .ref import quant_matmul_ref


def quant_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    x_scale: jnp.ndarray,
    w_scale: jnp.ndarray,
    use_pallas: bool = False,
    interpret: bool | None = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
) -> jnp.ndarray:
    """y = (x_int8 @ w_int8) * x_scale[:,None] * w_scale[None,:].

    Pads M/N/K up to block multiples for the Pallas path (zero padding is
    exact for integer matmul)."""
    if not use_pallas:
        return quant_matmul_ref(x, w, x_scale, w_scale)
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
        x_scale = jnp.pad(x_scale, (0, pm))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
        w_scale = jnp.pad(w_scale, (0, pn))
    y = quant_matmul_pallas(
        x, w, x_scale, w_scale,
        block_m=bm, block_n=bn, block_k=bk, interpret=interpret,
    )
    return y[:m, :n]
