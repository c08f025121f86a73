"""Pallas kernels: adder-graph executor (da4ml) and the LM stack's
flash-attention, selective-scan and W8A8 matmul kernels.

Every kernel decides interpret mode through :func:`resolve_interpret`,
so none runs its body in the Python interpreter on an accelerator.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for a ``pallas_call``: an explicit value wins;
    ``None`` means interpret only on the CPU backend, which has no
    Mosaic compiler (on a TPU the kernel is compiled)."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
