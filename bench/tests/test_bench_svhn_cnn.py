"""The svhn_cnn configuration at the published 32x32x3 SVHN frame: the
program builds its network, its work is the hand count, and the
reference agrees with the compiled design where a pool drops a remainder
row and column; the program's float forward pass rounds the bias after
the average pool where the compiler does."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench.harness import check, network, reference, runner, work

ROOT = Path(__file__).resolve().parents[2]
CONFIG = ROOT / "bench" / "configs" / "svhn_cnn.json"
SVHN_30 = Path(__file__).resolve().parent / "data" / "svhn_cnn_30.json"


def _config(side=32):
    cfg = json.loads(CONFIG.read_text())
    return {**cfg, "in_shape": [side, side, 3]}


def test_program_builds_the_configured_network():
    model, in_shape, in_quant = network.program_model(_config())
    assert in_shape == (32, 32, 3)
    assert len(model) == len(_config()["layers"])


# 32x32x3, VALID 3x3 convolutions, 2x2 pools dropping a remainder:
#   convolutions: 30*30*27*16 + 13*13*144*16 + 4*4*144*24 = 833,472
#   dense after the average pool's 2x2x24: 96*42 + 42*64 + 64*10 = 7,360
# weight bytes are svhn_cnn_30's: the frame changes no layer's weights
def test_work_is_the_hand_count():
    cfg = _config()
    assert work.macs_per_event(cfg) == 840_832
    assert work.bytes_per_call(cfg, 0, 2) == 14_240
    assert work.bytes_per_call(cfg, 16_384, 2) == 16_384 * (32 * 32 * 3 * 2 + 10 * 4) + 14_240


def _compiled(cfg, root):
    """The program's design of ``cfg``'s network at its ``in_shape``,
    from the configuration's weights."""
    from repro.flow import CompileConfig, Flow
    from repro.nn import models

    model, _, in_quant = models.svhn_cnn()
    params = network.make_params(cfg, root)
    design = Flow.compile(model, params, tuple(cfg["in_shape"]), in_quant,
                          config=CompileConfig(verify="cheap"))
    return model, params, in_quant, design


@pytest.fixture(scope="module")
def frames(tmp_path_factory):
    """side -> (config, params, design) at a 25x25x3 frame, whose first
    and second pools drop a row and column, and the full 32x32x3 one."""
    root = tmp_path_factory.mktemp("svhn")
    shutil.copytree(ROOT / "bench" / "layers", root / "bench" / "layers")
    out = {}
    for side in (25, 32):
        cfg = _config(side)
        _, params, _, design = _compiled(cfg, root)
        out[side] = (cfg, params, design, root)
    return out


@pytest.mark.parametrize("batch", [1, 144, 256])
@pytest.mark.parametrize("side", [25, 32])
def test_reference_matches_the_program_where_pools_drop_a_remainder(frames, side, batch):
    cfg, params, design, root = frames[side]
    assert any(p["b"].any() for p in params if "b" in p)
    x = runner.events(cfg, batch, np.random.default_rng(side * 1000 + batch))
    y = np.asarray(jax.jit(design.forward_int)(x))
    want = reference.forward(cfg, params, x, "float64", root)
    assert check.mismatched(y, check.output_scale(design), want) == 0


@pytest.fixture(scope="module")
def svhn_30(tmp_path_factory):
    root = tmp_path_factory.mktemp("svhn_30")
    shutil.copytree(ROOT / "bench" / "layers", root / "bench" / "layers")
    cfg = json.loads(SVHN_30.read_text())
    return cfg, _compiled(cfg, root)


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_float_forward_rounds_the_bias_after_the_average_pool_as_compiled(svhn_30, seed):
    from repro.nn import apply_model

    cfg, (model, params, in_quant, design) = svhn_30
    x = runner.events(cfg, 64, np.random.default_rng(seed))
    with jax.enable_x64(True):  # the float forward pass is exact in float64
        xf = jnp.asarray(x, jnp.float64) * in_quant.step
        got = np.asarray(jax.jit(lambda x: apply_model(params, model, x, in_quant=in_quant))(xf))
        want = np.asarray(jax.jit(design.forward)(xf), np.float64)
    assert int((got != want).any(axis=1).sum()) == 0
