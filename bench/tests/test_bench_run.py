"""Whole runs of the benchmark on the CPU, with the look for a chip skipped:
the result line's shape, the comparison with the reference, faults planted
in the timed path, a cell added with data files alone, layer kinds read
from the checkout, and the command's refusal to report anything without a
TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import control
from bench.harness import check, network, reference, runner

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SEED = 2**31 + 12345  # seeds past 32 signed bits must work


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the benchmark's data files, plus one cell added
    by data alone: a small-chunk bulk mix and a per-layer metric."""
    r = tmp_path_factory.mktemp("checkout")
    for d in ("configs", "traffic", "metrics", "layers"):
        shutil.copytree(ROOT / "bench" / d, r / "bench" / d)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (r / "bench" / "traffic" / "chunks_256.json").write_text(
        json.dumps({"kind": "closed_chunks", "chunk_events": 256, "pool_chunks": 4})
    )
    (r / "bench" / "metrics" / "engine.batches.online.py").write_text(
        "def read(rec):\n    return rec.batches or None\n"
    )
    spec["workloads"].append({"name": "jet_tagger.bulk_small", "config": "jet_tagger",
                              "traffic": "chunks_256", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append("jet_tagger.bulk_small")
    spec["per_layer"].append({"name": "engine.batches.online", "unit": "batches",
                              "better": "higher", "source": "program_counter",
                              "layer": "engine", "moves": "p50_ms",
                              "workloads": ["jet_tagger.online"]})
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    return r


def _run(root, cell, trace=False, seconds=0.3):
    return runner.run_cell(cell, SEED, seconds, trace, root=root, require_tpu=False)


def _shape(line):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and isinstance(v["value"], float)
    assert line["checks"] == {k: {"value": 0, "limit": 0} for k in check.LIMITS}
    json.dumps(line)


@pytest.mark.parametrize(
    ("cell", "e2e"),
    [("jet_tagger.online", {"p50_ms", "setup_s"}),
     ("jet_tagger.bulk_small", {"events_per_s", "setup_s"})],
)
def test_rehearsal_end_to_end_line(root, cell, e2e):
    line = _run(root, cell)
    _shape(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == e2e
    assert line["device"]["platform"] == "cpu"


def test_rehearsal_traced_line_has_no_device_numbers_off_a_tpu(root):
    online = _run(root, "jet_tagger.online", trace=True)
    _shape(online)
    # host-side layers are read; nothing of the device is, and no idle share
    assert set(online["metrics"]) == {
        "client.p95_ms.online", "client.lag_p99_ms.online", "engine.queue_wait_us.online",
        "engine.dispatch_us.online", "engine.batches.online",
    }
    assert "busy_s" not in online["device"] and "breakdown" not in online
    bulk = _run(root, "jet_tagger.bulk_small", trace=True)
    assert bulk["correct"] is True and bulk["metrics"] == {}


def _altered_answer(monkeypatch):
    from repro.nn.compiler import CompiledDesign

    orig = CompiledDesign.forward_int

    def altered(self, x):
        return orig(self, x).at[0, 0].add(1)

    monkeypatch.setattr(CompiledDesign, "forward_int", altered)


@pytest.mark.parametrize("cell", ["jet_tagger.online", "jet_tagger.bulk_small"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(root, cell, monkeypatch):
    _altered_answer(monkeypatch)
    line = _run(root, cell)
    assert line["correct"] is False
    assert line["checks"]["mismatched"]["value"] >= 1


def test_a_kind_file_altered_in_the_checkout_is_not_correct(root, tmp_path):
    """The reference reads each layer kind from the checkout it is given:
    a ``relu.py`` there whose outputs lie one grid step high fails the
    run, with no module of the harness edited."""
    altered = tmp_path / "checkout"
    shutil.copytree(root, altered)
    relu = altered / "bench" / "layers" / "relu.py"
    relu.write_text(relu.read_text() + """

from bench.harness.reference import grid

_forward = forward


def forward(x, p, layer, cur, precision, seq):
    x, cur = _forward(x, p, layer, cur, precision, seq)
    return x + 2.0 ** grid(cur)[2], cur
""")
    line = _run(altered, "jet_tagger.bulk_small")
    assert line["correct"] is False
    assert line["checks"]["mismatched"]["value"] > 0


def test_failed_requests_are_not_correct(root, monkeypatch):
    from repro.flow import Deployment

    orig = Deployment.submit
    calls = []

    def flaky(self, name, x, deadline_s=None):
        calls.append(1)
        if len(calls) % 97 == 0:
            raise RuntimeError("refused")
        return orig(self, name, x, deadline_s)

    monkeypatch.setattr(Deployment, "submit", flaky)
    line = _run(root, "jet_tagger.online")
    assert line["correct"] is False and line["failed"] >= 1
    assert line["checks"]["mismatched"]["value"] == 0


def test_control_is_not_correct(root):
    """The reference in bfloat16, in the program's place, fails the
    comparison; in float32 it is exact on these networks."""
    r = control.readings("jet_tagger.online", SEED, 0.3, 0, root)
    rate = runner.load_cell("jet_tagger.online", root).traffic["rate_per_s"]
    assert r["answers"] == round(rate * 0.3)
    assert r["bfloat16"]["correct"] is False and r["bfloat16"]["mismatched"] > 0
    assert r["float32"] == {"mismatched": 0, "correct": True}
    r = control.readings("jet_tagger.bulk_small", SEED, 0.3, 3, root)
    assert r["answers"] == 768 and r["bfloat16"]["correct"] is False


def _small_mixer(config):
    """The mixer configuration at 8 particles x 8 features, for the CPU."""
    cfg = json.loads(json.dumps(config))
    cfg["name"] = "mlp_mixer_small"
    cfg["program"]["kwargs"] = {"n_particles": 8, "n_features": 8, "d_ff": 8}
    cfg["in_shape"] = [8, 8]

    def shrink(layers):
        for layer in layers:
            if layer["kind"] == "residual":
                shrink(layer["body"])
            elif layer["kind"] in ("dense", "dense_on_axis") and layer["units"] in (16, 64):
                layer["units"] = 8
    shrink(cfg["layers"])
    return cfg


@pytest.mark.parametrize("name", ["jet_tagger", "mlp_mixer_small"])
def test_reference_matches_the_program_and_its_control_does_not(root, name):
    cfg = json.loads((ROOT / "bench" / "configs" / "jet_tagger.json").read_text())
    if name == "mlp_mixer_small":
        cfg = _small_mixer(json.loads(
            (ROOT / "bench" / "configs" / "mlp_mixer_jet.json").read_text()))
    design, _ = network.load_design(cfg, root)
    x = runner.events(cfg, 256, np.random.default_rng(7))
    assert x.dtype == np.int8
    y = np.asarray(design.forward_int(x))
    params = network.make_params(cfg, root)
    scale = check.output_scale(design)

    def want(precision):
        return reference.forward(cfg, params, x, precision, root)

    assert check.mismatched(y, scale, want("float64")) == 0
    assert check.mismatched(y, scale, want("float32")) == 0
    assert check.mismatched(y, scale, want("bfloat16")) > 0


@pytest.mark.parametrize(
    ("path", "layer", "key", "value"),
    [(ROOT / "bench" / "configs" / "jet_tagger.json", 0, "units", 63),
     (DATA / "svhn_cnn_30.json", 3, "filters", 17)],
    ids=["jet_tagger", "svhn_cnn_30"],
)
def test_program_model_must_be_the_configured_network(path, layer, key, value):
    cfg = json.loads(path.read_text())
    cfg["layers"][layer][key] = value
    with pytest.raises(ValueError, match="does not build the network"):
        network.program_model(cfg)


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "jet_tagger.online",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr
