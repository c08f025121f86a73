"""The layer kinds of the configuration format, one file each.

A layer ``{"kind": k, ...}`` of a configuration's ``layers`` is described
by ``bench/layers/<k>.py`` of the checkout the run was given, found by
that name and loaded by path, as a per-layer metric's reader is.  A new
kind is a new file, never an edit of the harness.  Each file gives:

- ``PROGRAM``: the name of the ``repro.nn.layers`` class it describes;
- ``describe(spec, seq) -> dict``: that class's spec in the
  configuration's vocabulary, without the ``kind`` key;
- ``init(layer, shape, rng, wcfg, seq) -> (params, shape)``: the layer's
  float weights, drawn from ``rng`` in a fixed order, and the shape of
  one event after it;
- ``forward(x, p, layer, cur, precision, seq) -> (x, cur)``: the plain
  numpy reference over a block of events ``x`` [n, *shape] of real
  values on the grid ``cur`` (a quant dict, or ``None`` where no single
  grid holds them), returning the outputs and their grid;
- ``work(layer, shape, seq) -> (macs, weight_bytes, shape)``: one event's
  multiply-accumulates, the bytes of the layer's weights, and the shape
  after it.

``seq`` is the harness's own walk over a list of layers, for a kind that
holds a body of layers: ``seq(specs)`` in ``describe``, ``seq(layers,
shape)`` in ``init`` and ``work``, ``seq(layers, params, x, cur)`` in
``forward``; each returns what the kind's function returns, for the list.
"""

from __future__ import annotations

import importlib.util
import re
from pathlib import Path

_NAME = re.compile(r"[A-Za-z0-9_]+")


def _dir(root: Path) -> Path:
    return Path(root) / "bench" / "layers"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_layer_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind(name: str, root: Path):
    """The module of layer kind ``name`` in the checkout at ``root``."""
    path = _dir(root) / f"{name}.py"
    if not _NAME.fullmatch(name) or not path.is_file():
        raise ValueError(f"no layer kind {name!r}: {path} does not exist")
    return _load(path)


def of_program(cls: str, root: Path) -> tuple[str, object]:
    """(name, module) of the kind whose ``PROGRAM`` is class ``cls``."""
    for path in sorted(_dir(root).glob("*.py")):
        mod = kind(path.stem, root)
        if mod.PROGRAM == cls:
            return path.stem, mod
    raise ValueError(
        f"the configuration format has no layer for {cls}: no file under "
        f"{_dir(root)} gives PROGRAM = {cls!r}"
    )
