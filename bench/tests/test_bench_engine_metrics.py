"""The engine's stage readers and the client's latency readers on
fabricated records: bookkeeping time per batch, the dispatcher's busy
share, the request tail and the generator's lag, and no reading where
the record does not hold what they read."""

import dataclasses

import numpy as np
import pytest

from bench.harness import runner
from bench.harness.cell import ROOT, load_cell

# per_stage deltas of one window, (seconds, count), as the runner passes them
STAGES = {
    "queue_wait": (40.0, 200_000),
    "idle": (0.9, 5_000),
    "batch_form": (1.1, 5_000),
    "pad": (0.5, 5_000),
    "dispatch": (6.0, 5_000),
    "copy_out": (1.0, 5_000),
    "observe": (0.5, 5_000),
}
# what an engine without the idle and observe stages reports
OLD_STAGES = {k: v for k, v in STAGES.items() if k not in ("idle", "observe")}


def _record(stage_s, shards=1, seconds=10.0):
    cell = load_cell("jet_tagger.online")
    traffic = dict(cell.traffic, serve=dict(cell.traffic["serve"], shards=shards))
    return runner.Record(cell=dataclasses.replace(cell, traffic=traffic), device_kind="cpu",
                         seconds=seconds, events=200_000, batches=5_000, stage_s=stage_s)


def _read(name, rec):
    return runner._read_metric(ROOT, name, rec)


@pytest.mark.parametrize(
    ("name", "shards", "want"),
    [("engine.observe_us.online", 1, 100.0),
     ("engine.busy_pct.online", 1, 80.0),  # (0.5 + 6 + 1 + 0.5) s of 10 s
     ("engine.busy_pct.online", 2, 40.0)],  # two dispatcher threads share the window
)
def test_engine_stage_readers(name, shards, want):
    assert _read(name, _record(STAGES, shards)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["engine.observe_us.online", "engine.busy_pct.online"])
@pytest.mark.parametrize("stage_s", [OLD_STAGES, None], ids=["without_the_stage", "bulk"])
def test_engine_stage_readers_without_the_stage(name, stage_s):
    assert _read(name, _record(stage_s)) is None


def test_client_readers_take_every_request_of_the_window():
    # 95 requests at 1 ms and 5 at 1 s: the 95th percentile lies between them
    lat = np.r_[np.full(95, 1e-3), np.full(5, 1.0)]
    rec = dataclasses.replace(_record(STAGES), latency_s=lat, lag_s=lat)
    assert _read("client.p95_ms.online", rec) == pytest.approx(np.percentile(lat, 95) * 1e3)
    assert _read("client.lag_p99_ms.online", rec) == pytest.approx(np.percentile(lat, 99) * 1e3)
    for name in ("client.p95_ms.online", "client.lag_p99_ms.online"):
        assert _read(name, _record(None)) is None
