"""Public attention op: Pallas kernel on TPU, fused-jnp oracle elsewhere.

The LM stack calls :func:`flash_attention`; backend selection is explicit
so the multi-pod dry-run (CPU lowering) always takes the jnp path while
TPU deployments flip ``use_pallas=True`` per config.

``offset`` is the absolute position of the first query token: None means
end-aligned (training/prefill without cache, offset = Sk - Sq); decode
into a preallocated cache passes the current write position so unwritten
cache slots are masked out.
"""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import attention_ref


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    scale: float | None = None,
    offset=None,
    use_pallas: bool = False,
    interpret: bool | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """GQA attention. q: [B,Hq,Sq,D]; k/v: [B,Hkv,Sk,D] with Hq % Hkv == 0."""
    if use_pallas:
        off = (k.shape[2] - q.shape[2]) if offset is None else offset
        return flash_attention_pallas(
            q, k, v, jnp.asarray(off, jnp.int32),
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    return attention_ref(q, k, v, causal=causal, scale=scale, offset=offset)
