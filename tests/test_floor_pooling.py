"""Floor pooling: a pool window that does not tile its input drops the
remainder row and column, in the compiled pipeline, the numpy
interpreter, the float forward pass and the static verifier alike.

svhn_cnn at a 25x25x3 frame drops a row and column at its first and
second pools (23 -> 11, 9 -> 4); at the published 32x32x3 frame at its
second (13 -> 6).  The biases are drawn nonzero, so a bias rounded onto
another grid than the compiler's shows.  The designs without pools
(jet_tagger, full-size mlp_mixer_jet) are pinned to the step specs and
outputs they compiled to before floor pooling.
"""

import copy
import dataclasses
import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.steps import check_steps
from repro.flow import CompileConfig, Flow
from repro.nn import apply_model, init_params, models
from repro.nn.interpreter import numpy_forward_fn

FRAMES = (25, 32)


def _svhn(side):
    model, _, in_quant = models.svhn_cnn(full_frame=True)
    return model, (side, side, 3), in_quant


def _nonzero_biases(params, seed):
    rng = np.random.default_rng(seed)
    return [
        {**p, "b": rng.uniform(-0.25, 0.25, size=p["b"].shape)}
        if "b" in p
        else p
        for p in params
    ]


@pytest.fixture(scope="module")
def svhn():
    """side -> (model, params, in_quant, design), compiled once a module."""
    out = {}
    for side in FRAMES:
        model, in_shape, in_quant = _svhn(side)
        params, _ = init_params(jax.random.PRNGKey(side), model, in_shape)
        params = _nonzero_biases(params, side)
        design = Flow.compile(model, params, in_shape, in_quant,
                              config=CompileConfig(verify="cheap"))
        out[side] = (model, params, in_quant, design)
    return out


def test_full_frame_is_the_published_frame():
    assert models.svhn_cnn(full_frame=True)[1] == (32, 32, 3)
    assert models.svhn_cnn()[1] == (30, 30, 3)


@pytest.mark.parametrize("side", FRAMES)
def test_pool_shapes_drop_the_remainder(svhn, side):
    design = svhn[side][3]
    pools = [(s.params["h"], s.params["h"] // s.params["ph"])
             for s in design.step_specs if s.kind in ("maxpool", "avgpool")]
    assert pools == {25: [(23, 11), (9, 4), (2, 1)], 32: [(30, 15), (13, 6), (4, 2)]}[side]
    assert design.out_shape == (10,)


@pytest.mark.parametrize("batch", [1, 144, 256])
@pytest.mark.parametrize("side", FRAMES)
def test_compiled_interpreter_and_float_forward_agree(svhn, side, batch):
    model, params, in_quant, design = svhn[side]
    q = in_quant.qint
    x = np.random.default_rng(batch).integers(
        q.lo, q.hi + 1, size=(batch, *design.in_shape)).astype(np.int32)
    x[0] = q.hi  # a saturated frame
    y = np.asarray(jax.jit(design.forward_int)(x))
    assert y.shape == (batch, 10)
    np.testing.assert_array_equal(y, numpy_forward_fn(design)(x))
    with jax.enable_x64(True):  # the float forward pass is exact in float64
        xf = jnp.asarray(x, jnp.float64) * in_quant.step
        np.testing.assert_array_equal(
            np.asarray(jax.jit(design.forward)(xf), np.float64),
            np.asarray(jax.jit(lambda x: apply_model(params, model, x, in_quant=in_quant))(xf)),
        )


def test_cmvm_steps_count_each_step_and_its_executor(svhn):
    design = svhn[32][3]
    steps = design.cmvm_steps
    assert [(s.step, s.kind, s.executor, s.instances, s.macs) for s in steps] == [
        # a proven conv step runs as one convolution
        (0, "conv", "conv", 30 * 30, 900 * 27 * 16),
        (4, "conv", "conv", 13 * 13, 169 * 144 * 16),
        (8, "conv", "conv", 4 * 4, 16 * 144 * 24),
        # the average pool's sums reach 4 x 255: past the dot's exact range
        (12, "dense", "adder_graph", 1, 96 * 42),
        (15, "dense", "dot", 1, 42 * 64),
        (18, "dense", "dot", 1, 64 * 10),
    ]
    assert [design.step_specs[s.step].kind for s in steps] == [s.kind for s in steps]
    assert design.executors == [s.executor for s in steps]
    assert design.dot_share == pytest.approx(1 - 4032 / 840_832)
    rows = design.summary().splitlines()
    assert rows[0].split()[-2:] == ["MACs/ev", "exec"]
    assert [r.split()[-2:] for r in rows[2:8]] == [[str(s.macs), s.executor] for s in steps]


def _with_pool_param(design, n, **params):
    """The design with its ``n``-th pool step's params overridden."""
    specs = copy.deepcopy(design.step_specs)
    pool = [s for s in specs if s.kind in ("maxpool", "avgpool")][n]
    pool.params.update(params)
    return dataclasses.replace(design, step_specs=specs)


def test_verifier_takes_a_remainder_and_refuses_a_broken_flow(svhn):
    design = svhn[32][3]
    assert not check_steps(design).errors
    # the second pool's flow is 13x13x16: a declared 14 rows is a broken flow
    assert "DA021" in check_steps(_with_pool_param(design, 1, h=14)).codes()
    # the last pool's input is 4x4x24: a 5x5 window holds no whole block
    assert "DA021" in check_steps(_with_pool_param(design, 2, ph=5, pw=5)).codes()


def _spec_blob(specs):
    return [
        {"kind": s.kind, "params": s.params, "table": s.table,
         "arrays": {k: np.asarray(v).tolist() for k, v in sorted(s.arrays.items())},
         "body": _spec_blob(s.body) if s.body else None}
        for s in specs
    ]


# sha256 of the step specs and of the int32 outputs on 144 seed-3 events,
# from weights init_params(PRNGKey(0)), as compiled before floor pooling
PINNED = {
    "jet_tagger": (
        models.jet_tagger,
        "5eca7445f1634909b85df547b6d3e6d1a100aea6c978ad571abe930e4bfdb170",
        "3c341fc44d1d6f2c65171006e855695ab86308705b8ea0988033e32bcbce383e",
    ),
    "mlp_mixer_jet": (
        lambda: models.mlp_mixer_jet(full_size=True),
        "d276f89f7dd9cf60205b42775948c973b9771e2a63ecb0580fcfbaefced9ab2f",
        "34ccc85b6bbdb01ea11c7e4f42b4901047dd829f5245ac072dc7c5ff53645657",
    ),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_designs_without_pools_are_unchanged(name):
    builder, specs_sha, out_sha = PINNED[name]
    model, in_shape, in_quant = builder()
    params, _ = init_params(jax.random.PRNGKey(0), model, in_shape)
    design = Flow.compile(model, params, in_shape, in_quant,
                          config=CompileConfig(verify="cheap"))
    blob = json.dumps(_spec_blob(design.step_specs), sort_keys=True).encode()
    assert hashlib.sha256(blob).hexdigest() == specs_sha
    q = in_quant.qint
    x = np.random.default_rng(3).integers(q.lo, q.hi + 1, size=(144, *in_shape)).astype(np.int32)
    y = np.asarray(jax.jit(design.forward_int)(x))
    assert y.dtype == np.int32
    assert hashlib.sha256(np.ascontiguousarray(y).tobytes()).hexdigest() == out_sha
