"""The layer kinds under ``bench/layers/``: the benchmarked configurations'
weights and reference outputs pinned bit for bit, a conv-and-pool network
checked against the program, and a kind with no file refused by name."""

import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench.harness import check, kinds, network, reference, runner, work

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "bench" / "configs"
SVHN = Path(__file__).resolve().parent / "data" / "svhn_cnn_30.json"

# sha256 of the weights and of the reference's outputs on 256 events drawn
# with seed 7.  A cached design is found by a digest of the configuration
# alone, so weights drawn otherwise would be compared with a design
# compiled from these.
PARAMS_SHA = {
    "jet_tagger": "53ee4491bd805ffd9dc9163afc9c34d3fbff9ab10d9754f546ecbf60dccc2eb9",
    "mlp_mixer_jet": "b305c6bdeb7c71dad8b1d2d4084f7bdc19febda954b8c679a82c4264280ab280",
}
OUTPUT_SHA = {
    ("jet_tagger", "float64"): "df8fb318c8d72de350b9635cc748f49c77df92f86ec01a124b307583b3b9b76f",
    ("jet_tagger", "float32"): "df8fb318c8d72de350b9635cc748f49c77df92f86ec01a124b307583b3b9b76f",
    ("jet_tagger", "bfloat16"): "5511febdcbb1162ce3de28d22056859218bce0bb51cf0006868ebd25fe59966c",
    ("mlp_mixer_jet", "float64"): "bb119e5d415b66ee5a53f32c1365796a72fb5475c3d1dff54d96c48f8ac73122",
    ("mlp_mixer_jet", "float32"): "bb119e5d415b66ee5a53f32c1365796a72fb5475c3d1dff54d96c48f8ac73122",
    ("mlp_mixer_jet", "bfloat16"): "070e353026a8f9c0c45a01401a0b3ab4e3f1f1bb05beb73a05a45f7ddbd54799",
}


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _sha(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _leaves(params):
    for p in params:
        for k in sorted(p):
            yield from _leaves(p[k]) if k == "body" else [p[k]]


@pytest.mark.parametrize("name", sorted(PARAMS_SHA))
def test_make_params_is_the_parents(name):
    assert _sha(_leaves(network.make_params(_config(name)))) == PARAMS_SHA[name]


@pytest.mark.parametrize(("name", "precision"), sorted(OUTPUT_SHA))
def test_reference_outputs_are_the_parents(name, precision):
    cfg = _config(name)
    x = runner.events(cfg, 256, np.random.default_rng(7))
    y = reference.forward(cfg, network.make_params(cfg), x, precision)
    assert _sha([y]) == OUTPUT_SHA[(name, precision)]


def _unknown_kind():
    cfg = _config("jet_tagger")
    cfg["layers"][0] = {"kind": "gelu"}
    return cfg


@pytest.mark.parametrize(
    "call",
    [lambda c: network.make_params(c),
     lambda c: reference.forward(c, [{}] * len(c["layers"]), np.zeros((1, 16), np.int8)),
     lambda c: work.macs_per_event(c)],
    ids=["make_params", "reference", "work"],
)
def test_a_kind_with_no_file_is_refused_with_the_files_name(call):
    with pytest.raises(ValueError, match=r"no layer kind 'gelu': .*bench/layers/gelu\.py"):
        call(_unknown_kind())


def test_a_program_layer_with_no_kind_file_is_refused(tmp_path):
    shutil.copytree(ROOT / "bench" / "layers", tmp_path / "bench" / "layers")
    (tmp_path / "bench" / "layers" / "conv2d.py").unlink()
    with pytest.raises(ValueError, match="no layer for QConv2D"):
        network.program_model(json.loads(SVHN.read_text()), tmp_path)
    assert kinds.of_program("ReLU", tmp_path)[0] == "relu"


@pytest.fixture(scope="module")
def svhn(tmp_path_factory):
    """The conv-and-pool configuration, its weights and its design,
    compiled once for the module into a checkout of its own."""
    root = tmp_path_factory.mktemp("svhn")
    shutil.copytree(ROOT / "bench" / "layers", root / "bench" / "layers")
    cfg = json.loads(SVHN.read_text())
    design, _ = network.load_design(cfg, root)
    return cfg, network.make_params(cfg, root), design, root


@pytest.mark.parametrize(("precision", "correct"),
                         [("float64", True), ("float32", True), ("bfloat16", False)])
def test_conv_and_pool_reference_matches_the_program_and_its_control_does_not(
        svhn, precision, correct):
    cfg, params, design, root = svhn
    x = runner.events(cfg, 64, np.random.default_rng(7))
    assert x.dtype == np.int16  # the unsigned 8-bit grid does not fit int8
    y = np.asarray(design.forward_int(x))
    want = reference.forward(cfg, params, x, precision, root)
    mism = check.mismatched(y, check.output_scale(design), want)
    assert (mism == 0) is correct

