"""Public selective-scan op: Pallas kernel on TPU, jnp scan elsewhere."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import selective_scan_pallas
from .ref import selective_scan_ref


def selective_scan(
    dt: jnp.ndarray,
    bmat: jnp.ndarray,
    cmat: jnp.ndarray,
    x: jnp.ndarray,
    a: jnp.ndarray,
    h0: jnp.ndarray,
    use_pallas: bool = False,
    tile_d: int = 128,
    interpret: bool | None = None,
):
    """Mamba-1 recurrence. Returns (y [B,S,D], h_final [B,D,N])."""
    # kernel contract is f32 (the scan state must be f32 regardless of
    # the surrounding compute dtype / x64 mode)
    f32 = jnp.float32
    dt, bmat, cmat, x, a, h0 = (
        u.astype(f32) for u in (dt, bmat, cmat, x, a, h0)
    )
    if use_pallas:
        return selective_scan_pallas(
            dt, bmat, cmat, x, a, h0, tile_d=tile_d, interpret=interpret
        )
    return selective_scan_ref(dt, bmat, cmat, x, a, h0)
