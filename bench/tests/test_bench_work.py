"""Ops and bytes of the configurations, against counts made by hand."""

import json
from pathlib import Path

import pytest

from bench.harness import work
from bench.harness.peaks import peak

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
DATA = Path(__file__).resolve().parent / "data"


def _config(name):
    path = CONFIGS / f"{name}.json"
    return json.loads((path if path.exists() else DATA / f"{name}.json").read_text())


# jet_tagger: 16*64 + 64*32 + 32*16 + 16*16 + 16*5
# mlp_mixer_jet (64 particles x 16 features, d_ff 16):
#   feature MLPs (before and inside the skip): 4 x 64*(16*16) = 65,536
#   particle MLPs (inside the skip and after it): 4 x 16*(64*64) = 262,144
#   head: 1024*32 + 32*5 = 32,928
# svhn_cnn_30 (test data; 30x30x3, VALID 3x3 convolutions, 2x2 pools):
#   convolutions: 28*28*27*16 + 12*12*144*16 + 4*4*144*24 = 725,760
#   dense after the average pool's 2x2x24: 96*42 + 42*64 + 64*10 = 7,360
@pytest.mark.parametrize(
    ("name", "macs"),
    [("jet_tagger", 3_920), ("mlp_mixer_jet", 360_608), ("svhn_cnn_30", 733_120)],
)
def test_macs_per_event_match_hand_counts(name, macs):
    cfg = _config(name)
    assert work.macs_per_event(cfg) == macs
    assert work.ops_per_event(cfg) == 2 * macs


def test_bytes_per_call_counts_inputs_outputs_and_weights_once():
    cfg = _config("jet_tagger")
    weights = 3_920 + 4 * (64 + 32 + 16 + 16 + 5)  # int8 weights, int32 biases
    assert work.bytes_per_call(cfg, 1, 1) == 16 + 5 * 4 + weights
    assert work.bytes_per_call(cfg, 65_536, 1) == 65_536 * (16 + 20) + weights
    assert work.bytes_per_call(cfg, 256, 4) == 256 * (64 + 20) + weights


# int8 weights and int32 biases, once a call:
#   mlp_mixer_jet: 4 x (16*16 + 4*16) + 4 x (64*64 + 4*64) + 1024*32 + 4*32 + 32*5 + 4*5
#   svhn_cnn_30: (27*16 + 144*16 + 144*24) + 4*(16 + 16 + 24)
#                + (96*42 + 42*64 + 64*10) + 4*(42 + 64 + 10)
@pytest.mark.parametrize(
    ("name", "wbytes"),
    [("jet_tagger", 3_920 + 4 * 133), ("mlp_mixer_jet", 51_764), ("svhn_cnn_30", 14_240)],
)
def test_weight_bytes_match_hand_counts(name, wbytes):
    assert work.bytes_per_call(_config(name), 0, 1) == wbytes


def test_least_time_picks_the_larger_bound():
    v5e = peak("TPU v5 lite")
    t, bound = work.least_time_s(_config("jet_tagger"), 65_536, 1, v5e)
    assert bound == "memory"  # 36 bytes against 7,840 ops an event
    assert t == pytest.approx(work.bytes_per_call(_config("jet_tagger"), 65_536, 1) / 819e9)
    t, bound = work.least_time_s(_config("mlp_mixer_jet"), 4_096, 1, v5e)
    assert bound == "compute"
    assert t == pytest.approx(2 * 360_608 * 4_096 / 393e12)


def test_unknown_device_has_no_peak():
    with pytest.raises(KeyError):
        peak("cpu")
