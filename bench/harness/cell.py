"""Find a cell of ``BENCHMARK.json`` and everything it names, by name.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); its metrics are the
entries of ``end_to_end`` and ``per_layer`` that apply to it, and each
per-layer metric is read by ``bench/metrics/<metric>.py``.  Each kind of
layer a configuration's ``layers`` name is ``bench/layers/<kind>.py``
(``kinds.py``).  Adding any of these is a new file and a new entry,
never an edit.  All of them are read from the checkout the run was
given, ``root``.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1
    root: Path  # the checkout its files are read from


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(
        m
        for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    )
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer, Path(root))
