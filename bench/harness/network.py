"""The configuration's weights, and the program's design built from them.

The weights are drawn here, in numpy, from the configuration's
``weights.seed``: the program compiles them, and the reference reads the
same float arrays and quantizes them itself.  What each layer draws, and
how a program's layer spec reads in the configuration's vocabulary, is
its kind's file under ``bench/layers/`` (``kinds.py``).  The compiled
design is kept as an artifact under ``bench/.cache/designs/``, keyed by a
digest of the configuration, so that only the first run in a checkout
solves; later runs load it with zero solver calls.
"""

from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import numpy as np

from . import kinds
from .cell import ROOT


def quant_dict(q) -> dict | None:
    """A program's ``QuantConfig`` in the configuration's vocabulary."""
    if q is None:
        return None
    return {"bits": int(q.bits), "int_bits": int(q.int_bits), "signed": bool(q.signed)}


def glorot(rng, wcfg: dict, shape: tuple) -> dict:
    """Glorot-uniform float32 weights of ``shape``, whose fan-in is the
    product of all but its last axis, then one uniform bias per output."""
    lim = (3.0 / int(np.prod(shape[:-1]))) ** 0.5
    w = rng.uniform(-lim, lim, size=shape).astype(np.float32)
    b = rng.uniform(-wcfg["b_uniform"], wcfg["b_uniform"], size=shape[-1])
    return {"w": w, "b": b.astype(np.float32)}


def _make_layer_params(layers: list, shape: tuple, rng, wcfg: dict, root: Path):
    def seq(body, shape):
        return _make_layer_params(body, shape, rng, wcfg, root)

    params: list = []
    for layer in layers:
        p, shape = kinds.kind(layer["kind"], root).init(layer, shape, rng, wcfg, seq)
        params.append(p)
    return params, shape


def make_params(config: dict, root: Path = ROOT) -> list:
    """Float32 weights and biases for every layer of the configuration,
    from ``config["weights"]["seed"]``; nested the way the layers are."""
    wcfg = config["weights"]
    if wcfg["w"] != "glorot_uniform":
        raise ValueError(f"unknown weight init {wcfg['w']!r}")
    rng = np.random.default_rng(int(wcfg["seed"]))
    return _make_layer_params(config["layers"], tuple(config["in_shape"]), rng, wcfg, root)[0]


def _describe_program(model, root: Path) -> list:
    """The program's layer specs in the configuration's layer vocabulary."""

    def seq(body):
        return _describe_program(body, root)

    out = []
    for spec in model:
        name, mod = kinds.of_program(type(spec).__name__, root)
        out.append({"kind": name, **mod.describe(spec, seq)})
    return out


def program_model(config: dict, root: Path = ROOT):
    """The program's own model for this configuration, from
    ``repro.nn.models.<function>``; refuses one that is not the network
    the configuration states."""
    from repro.nn import models

    prog = config["program"]
    model, in_shape, in_quant = getattr(models, prog["function"])(**prog["kwargs"])
    got = {
        "in_shape": list(in_shape),
        "in_quant": quant_dict(in_quant),
        "layers": _describe_program(model, root),
    }
    want = {k: config[k] for k in got}
    if json.loads(json.dumps(got)) != want:
        raise ValueError(
            f"repro.nn.models.{prog['function']} does not build the network of "
            f"configuration {config['name']!r}"
        )
    return model, tuple(in_shape), in_quant


def digest(config: dict) -> str:
    keys = ("program", "in_shape", "in_quant", "layers", "weights")
    blob = json.dumps({k: config[k] for k in keys}, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def load_design(config: dict, root: Path):
    """(design, solved): the compiled design of the configuration, loaded
    from its artifact, or compiled and saved when there is none yet."""
    from repro.flow import CompileConfig, Flow
    from repro.runtime import ArtifactCorruptError

    path = Path(root) / "bench" / ".cache" / "designs" / f"{config['name']}-{digest(config)}"
    if (path / "manifest.json").exists():
        try:
            return Flow.load(path), False
        except ArtifactCorruptError:
            shutil.rmtree(path)
    model, in_shape, in_quant = program_model(config, root)
    design = Flow.compile(
        model, make_params(config, root), in_shape, in_quant, config=CompileConfig(verify="cheap")
    )
    design.save(path)
    return Flow.load(path), True
