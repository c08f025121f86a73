"""Ops and bytes of the configurations, against counts made by hand."""

import json
from pathlib import Path

import pytest

from bench.harness import work
from bench.harness.peaks import peak

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# jet_tagger: 16*64 + 64*32 + 32*16 + 16*16 + 16*5
# mlp_mixer_jet (64 particles x 16 features, d_ff 16):
#   feature MLPs (before and inside the skip): 4 x 64*(16*16) = 65,536
#   particle MLPs (inside the skip and after it): 4 x 16*(64*64) = 262,144
#   head: 1024*32 + 32*5 = 32,928
@pytest.mark.parametrize(
    ("name", "macs"), [("jet_tagger", 3_920), ("mlp_mixer_jet", 360_608)]
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
