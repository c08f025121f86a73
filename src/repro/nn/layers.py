"""Functional quantized NN layers (pure JAX, explicit param pytrees).

Models are ``Sequential`` tuples of frozen layer specs.  The float
forward path (``apply_model``) uses straight-through fixed-point fake
quantization and is *bit-compatible* with the compiled integer adder
graph (see compiler.py): floor rounding, saturation, power-of-two-exact
average pooling.  Run in float64 for exact equality; float32 training is
within 1 ulp of the hardware semantics.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .quant import QuantConfig, bit_count_surrogate, fake_quant

# ----------------------------------------------------------------------
# Layer specs
# ----------------------------------------------------------------------


@dataclass(frozen=True)
class QDense:
    units: int
    w_quant: QuantConfig = QuantConfig(8, 2)
    out_quant: QuantConfig | None = None  # activation re-quantization
    use_bias: bool = True


@dataclass(frozen=True)
class QDenseOnAxis:
    """Dense along a non-final axis (EinsumDense, e.g. MLP-Mixer token mix)."""

    units: int
    axis: int
    w_quant: QuantConfig = QuantConfig(8, 2)
    out_quant: QuantConfig | None = None
    use_bias: bool = True


@dataclass(frozen=True)
class QConv2D:
    filters: int
    kernel: tuple[int, int] = (3, 3)
    strides: tuple[int, int] = (1, 1)
    padding: str = "VALID"
    w_quant: QuantConfig = QuantConfig(8, 2)
    out_quant: QuantConfig | None = None
    use_bias: bool = True


@dataclass(frozen=True)
class ReLU:
    out_quant: QuantConfig | None = None


@dataclass(frozen=True)
class MaxPool2D:
    size: tuple[int, int] = (2, 2)


@dataclass(frozen=True)
class AvgPool2D:
    """Power-of-two window: exact on the grid (sum then exponent shift)."""

    size: tuple[int, int] = (2, 2)


@dataclass(frozen=True)
class Flatten:
    pass


@dataclass(frozen=True)
class Residual:
    """y = x + body(x) (MLP-Mixer skip connection)."""

    body: tuple = ()


LayerSpec = QDense | QDenseOnAxis | QConv2D | ReLU | MaxPool2D | AvgPool2D | Flatten | Residual
Sequential = tuple  # tuple[LayerSpec, ...]


# ----------------------------------------------------------------------
# Initialisation
# ----------------------------------------------------------------------
def _glorot(rng, shape):
    fan_in = int(np.prod(shape[:-1]))
    lim = (3.0 / fan_in) ** 0.5
    return jax.random.uniform(rng, shape, jnp.float32, -lim, lim)


def init_params(rng: jax.Array, model: Sequential, in_shape: tuple[int, ...]):
    """Returns (params_list, out_shape). in_shape excludes batch."""
    params: list[dict] = []
    shape = tuple(in_shape)
    for spec in model:
        rng, sub = jax.random.split(rng)
        if isinstance(spec, QDense):
            w = _glorot(sub, (shape[-1], spec.units))
            p = {"w": w}
            if spec.use_bias:
                p["b"] = jnp.zeros((spec.units,), jnp.float32)
            params.append(p)
            shape = shape[:-1] + (spec.units,)
        elif isinstance(spec, QDenseOnAxis):
            ax = spec.axis % len(shape)
            w = _glorot(sub, (shape[ax], spec.units))
            p = {"w": w}
            if spec.use_bias:
                p["b"] = jnp.zeros((spec.units,), jnp.float32)
            params.append(p)
            shape = tuple(spec.units if i == ax else s for i, s in enumerate(shape))
        elif isinstance(spec, QConv2D):
            kh, kw = spec.kernel
            cin = shape[-1]
            w = _glorot(sub, (kh, kw, cin, spec.filters))
            p = {"w": w}
            if spec.use_bias:
                p["b"] = jnp.zeros((spec.filters,), jnp.float32)
            params.append(p)
            h, wd = shape[0], shape[1]
            if spec.padding == "VALID":
                h = (h - kh) // spec.strides[0] + 1
                wd = (wd - kw) // spec.strides[1] + 1
            else:
                h = -(-h // spec.strides[0])
                wd = -(-wd // spec.strides[1])
            shape = (h, wd, spec.filters)
        elif isinstance(spec, (MaxPool2D, AvgPool2D)):
            params.append({})
            shape = (shape[0] // spec.size[0], shape[1] // spec.size[1], shape[2])
        elif isinstance(spec, Flatten):
            params.append({})
            shape = (int(np.prod(shape)),)
        elif isinstance(spec, ReLU):
            params.append({})
        elif isinstance(spec, Residual):
            sub_params, sub_shape = init_params(sub, spec.body, shape)
            assert sub_shape == shape, "residual body must preserve shape"
            params.append({"body": sub_params})
        else:
            raise TypeError(f"unknown layer spec {spec}")
    return params, shape


# ----------------------------------------------------------------------
# Forward pass (float, STE quantization)
# ----------------------------------------------------------------------
def _bias_quant(spec_w: QuantConfig, in_quant: QuantConfig) -> QuantConfig:
    """Bias lives on the accumulator grid (in_step * w_step), wide range."""
    exp = spec_w.scale_exp() + in_quant.scale_exp()
    bits = 24
    return QuantConfig(bits, bits + exp, True)


def apply_model(
    params: list,
    model: Sequential,
    x: jnp.ndarray,
    in_quant: QuantConfig | None = None,
    collect_bits: bool = False,
):
    """Run the float/STE forward pass.

    Every QDense/QConv input must already be on a known grid; pass
    ``in_quant`` to quantize the model input.  Returns y (and the
    bit-count regularisation penalty if collect_bits).
    """
    penalty = 0.0
    cur_quant = in_quant
    if in_quant is not None:
        x = fake_quant(x, in_quant)
    for spec, p in zip(model, params):
        if isinstance(spec, (QDense, QDenseOnAxis)):
            wq = fake_quant(p["w"], spec.w_quant, rounding="round")
            if collect_bits:
                penalty = penalty + bit_count_surrogate(p["w"], spec.w_quant)
            if isinstance(spec, QDenseOnAxis):
                ax = spec.axis % (x.ndim - 1) + 1  # feature axes exclude batch
                x = jnp.moveaxis(x, ax, -1)
                x = x @ wq
                x = jnp.moveaxis(x, -1, ax)
                bshape = tuple(
                    spec.units if i == ax else 1 for i in range(1, x.ndim)
                )
            else:
                x = x @ wq
                bshape = (spec.units,)
            if spec.use_bias and cur_quant is not None:
                bq = fake_quant(
                    p["b"], _bias_quant(spec.w_quant, cur_quant), rounding="round"
                )
                x = x + bq.reshape(bshape)
            elif spec.use_bias:
                x = x + p["b"].reshape(bshape)
            if spec.out_quant is not None:
                x = fake_quant(x, spec.out_quant)
                cur_quant = spec.out_quant
            else:
                cur_quant = None
        elif isinstance(spec, QConv2D):
            wq = fake_quant(p["w"], spec.w_quant, rounding="round")
            if collect_bits:
                penalty = penalty + bit_count_surrogate(p["w"], spec.w_quant)
            x = jax.lax.conv_general_dilated(
                x, wq.astype(x.dtype), spec.strides, spec.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            if spec.use_bias and cur_quant is not None:
                bq = fake_quant(p["b"], _bias_quant(spec.w_quant, cur_quant), rounding="round")
                x = x + bq
            elif spec.use_bias:
                x = x + p["b"]
            if spec.out_quant is not None:
                x = fake_quant(x, spec.out_quant)
                cur_quant = spec.out_quant
            else:
                cur_quant = None
        elif isinstance(spec, ReLU):
            x = jnp.maximum(x, 0.0)
            if spec.out_quant is not None:
                x = fake_quant(x, spec.out_quant)
                cur_quant = spec.out_quant
        elif isinstance(spec, MaxPool2D):
            x = _pool(x, spec.size, jax.lax.max, -jnp.inf)
        elif isinstance(spec, AvgPool2D):
            k = spec.size[0] * spec.size[1]
            assert k & (k - 1) == 0, "AvgPool window must be a power of two"
            x = _pool(x, spec.size, jax.lax.add, 0.0) / k
            if cur_quant is not None:
                # the mean of k values on a grid lies on a grid k times
                # finer, with the same range: the next bias rounds there
                s = k.bit_length() - 1
                cur_quant = QuantConfig(cur_quant.bits + s, cur_quant.int_bits, cur_quant.signed)
        elif isinstance(spec, Flatten):
            x = x.reshape(x.shape[0], -1)
        elif isinstance(spec, Residual):
            y = apply_model(p["body"], spec.body, x, in_quant=cur_quant)
            x = x + y
            cur_quant = None
        else:
            raise TypeError(f"unknown layer spec {spec}")
    if collect_bits:
        return x, penalty
    return x


def _pool(x, size, op, init):
    return jax.lax.reduce_window(
        x, init, op,
        window_dimensions=(1, size[0], size[1], 1),
        window_strides=(1, size[0], size[1], 1),
        padding="VALID",
    )
