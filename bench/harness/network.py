"""The configuration's weights, and the program's design built from them.

The weights are drawn here, in numpy, from the configuration's
``weights.seed``: the program compiles them, and the reference reads the
same float arrays and quantizes them itself.  The compiled design is
kept as an artifact under ``bench/.cache/designs/``, keyed by a digest
of the configuration, so that only the first run in a checkout solves;
later runs load it with zero solver calls.
"""

from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import numpy as np

_KIND_OF_CLASS = {
    "QDense": "dense",
    "QDenseOnAxis": "dense_on_axis",
    "ReLU": "relu",
    "Flatten": "flatten",
    "Residual": "residual",
}


def _quant_dict(q) -> dict | None:
    if q is None:
        return None
    return {"bits": int(q.bits), "int_bits": int(q.int_bits), "signed": bool(q.signed)}


def _make_layer_params(layers: list, shape: tuple, rng, wcfg: dict) -> tuple[list, tuple]:
    params: list = []
    for layer in layers:
        kind = layer["kind"]
        if kind in ("dense", "dense_on_axis"):
            ax = layer["axis"] if kind == "dense_on_axis" else len(shape) - 1
            fan_in, units = shape[ax], layer["units"]
            lim = (3.0 / fan_in) ** 0.5
            w = rng.uniform(-lim, lim, size=(fan_in, units)).astype(np.float32)
            b = rng.uniform(-wcfg["b_uniform"], wcfg["b_uniform"], size=units)
            params.append({"w": w, "b": b.astype(np.float32)})
            shape = tuple(units if i == ax else s for i, s in enumerate(shape))
        elif kind == "flatten":
            params.append({})
            shape = (int(np.prod(shape)),)
        elif kind == "relu":
            params.append({})
        elif kind == "residual":
            body, _ = _make_layer_params(layer["body"], shape, rng, wcfg)
            params.append({"body": body})
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return params, shape


def make_params(config: dict) -> list:
    """Float32 weights and biases for every layer of the configuration,
    from ``config["weights"]["seed"]``; nested the way the layers are."""
    wcfg = config["weights"]
    if wcfg["w"] != "glorot_uniform":
        raise ValueError(f"unknown weight init {wcfg['w']!r}")
    rng = np.random.default_rng(int(wcfg["seed"]))
    return _make_layer_params(config["layers"], tuple(config["in_shape"]), rng, wcfg)[0]


def _describe_program(model) -> list:
    """The program's layer specs in the configuration's layer vocabulary."""
    out = []
    for spec in model:
        cls = type(spec).__name__
        if cls not in _KIND_OF_CLASS:
            raise ValueError(f"the configuration format has no layer for {cls}")
        d: dict = {"kind": _KIND_OF_CLASS[cls]}
        if hasattr(spec, "units"):
            d["units"] = int(spec.units)
            d["w_quant"] = _quant_dict(spec.w_quant)
        if hasattr(spec, "axis"):
            d["axis"] = int(spec.axis)
        if getattr(spec, "out_quant", None) is not None:
            d["out_quant"] = _quant_dict(spec.out_quant)
        if cls == "Residual":
            d["body"] = _describe_program(spec.body)
        out.append(d)
    return out


def program_model(config: dict):
    """The program's own model for this configuration, from
    ``repro.nn.models.<function>``; refuses one that is not the network
    the configuration states."""
    from repro.nn import models

    prog = config["program"]
    model, in_shape, in_quant = getattr(models, prog["function"])(**prog["kwargs"])
    got = {
        "in_shape": list(in_shape),
        "in_quant": _quant_dict(in_quant),
        "layers": _describe_program(model),
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
    model, in_shape, in_quant = program_model(config)
    design = Flow.compile(
        model, make_params(config), in_shape, in_quant, config=CompileConfig(verify="cheap")
    )
    design.save(path)
    return Flow.load(path), True
