"""A proven conv step runs as one exact convolution.

Where ``dot_matrix`` proves a conv step's table, ``build_steps`` runs it
as one bfloat16 ``lax.conv_general_dilated`` of the table's matrix,
reshaped to the HWIO kernel, in place of kh·kw strided slices, a
concatenate and a dot.  Its int32 answers must equal, bit for bit, the
same specs built with no programs (the adder graph on the im2col
columns) and the numpy interpreter, over channel counts, kernel shapes,
strides, a per-channel shift and batches that hold a saturated frame.
A refused table keeps the im2col and the adder graph.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import solve_cmvm
from repro.core.fixed_point import QInterval
from repro.kernels.adder_graph import compile_tables
from repro.nn import CompiledDesign, StepSpec, build_steps
from repro.nn.interpreter import numpy_forward_fn

H, W, COUT = 9, 8, 5  # a frame that is not square, so h and w cannot swap unseen
PIXEL = QInterval(-256, 255, 0)  # |x| <= 256: the widest grid bfloat16 holds exactly


@functools.lru_cache(maxsize=None)
def _program(cin, kh, kw, grid=PIXEL):
    """The solved program of a 6-bit weight table with kh·kw·cin rows."""
    rng = np.random.default_rng(cin * 100 + kh * 10 + kw)
    m = rng.integers(-31, 32, size=(kh * kw * cin, COUT))
    return solve_cmvm(m, qint_in=[grid] * len(m)).program


def _design(cin, kernel, stride, shift, programs=True, grid=PIXEL):
    (kh, kw), (sh, sw) = kernel, stride
    oh, ow = (H - kh) // sh + 1, (W - kw) // sw + 1
    prog = _program(cin, kh, kw, grid)
    arrays = {"bias": np.arange(COUT, dtype=np.int64) * 37 - 80}
    if shift:
        arrays["shift"] = np.arange(COUT, dtype=np.int64) % 3
    spec = StepSpec(
        "conv",
        params={"h": H, "w": W, "cin": cin, "kh": kh, "kw": kw,
                "sh": sh, "sw": sw, "oh": oh, "ow": ow},
        arrays=arrays,
        table=0,
    )
    design = CompiledDesign(
        step_specs=[spec], tables=[compile_tables(prog)],
        programs=[prog.to_arrays()] if programs else [],
        in_shape=(H, W, cin), out_shape=(oh, ow, COUT),
    )
    design.steps = build_steps(design.step_specs, design.tables, programs=design.programs)
    return design


def _frames(batch, cin, seed):
    x = np.random.default_rng(seed).integers(
        PIXEL.lo, PIXEL.hi + 1, size=(batch, H, W, cin)).astype(np.int32)
    x[0] = PIXEL.hi  # a saturated frame
    if batch > 1:
        x[-1] = PIXEL.lo
    return x


@pytest.mark.parametrize("batch", [1, 144])
@pytest.mark.parametrize("shift", [False, True], ids=["no_shift", "shift"])
@pytest.mark.parametrize("stride", [(1, 1), (2, 2), (2, 1)], ids=["s1", "s2", "s2x1"])
@pytest.mark.parametrize("kernel", [(3, 3), (2, 3)], ids=["3x3", "2x3"])
@pytest.mark.parametrize("cin", [1, 3, 16])
def test_conv_step_equals_adder_graph_and_interpreter(cin, kernel, stride, shift, batch):
    design = _design(cin, kernel, stride, shift)
    adder = _design(cin, kernel, stride, shift, programs=False)
    assert design.executors == ["conv"]
    assert adder.executors == ["adder_graph"]
    (kh, kw), (sh, sw) = kernel, stride
    steps = design.cmvm_steps
    assert steps[0].macs == steps[0].instances * kh * kw * cin * COUT
    assert design.dot_share == 1.0 and adder.dot_share == 0.0

    x = _frames(batch, cin, seed=cin + 7 * kh + 11 * sh + batch)
    got = np.asarray(jax.jit(design.forward_int)(x))
    assert got.dtype == np.int32
    assert got.shape == (batch, (H - kh) // sh + 1, (W - kw) // sw + 1, COUT)
    np.testing.assert_array_equal(got, np.asarray(jax.jit(adder.forward_int)(x)))
    np.testing.assert_array_equal(got, numpy_forward_fn(design)(x))


def _ops(design, cin):
    x = jax.ShapeDtypeStruct((4, H, W, cin), jnp.int32)
    text = jax.jit(design.forward_int).lower(x).as_text()
    return {op: text.count(f"stablehlo.{op}") for op in ("convolution", "concatenate", "slice")}


def test_proven_conv_lowers_to_one_convolution_and_a_refused_one_to_slices():
    ops = _ops(_design(3, (3, 3), (1, 1), shift=True), 3)
    assert ops["convolution"] == 1
    assert ops["concatenate"] == ops["slice"] == 0

    # a 16-bit input grid is past what bfloat16 holds exactly: the proof
    # refuses the table, and the step keeps its kh·kw slices and the
    # adder graph
    wide = QInterval(-(2**15), 2**15 - 1, 0)
    refused = _design(3, (3, 3), (1, 1), shift=True, grid=wide)
    assert refused.executors == ["adder_graph"]
    ops = _ops(refused, 3)
    assert ops["convolution"] == 0
    assert ops["concatenate"] >= 1
    assert ops["slice"] >= 3 * 3
