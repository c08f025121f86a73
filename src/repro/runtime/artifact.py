"""Compiled-design artifacts: solve once, cold-start in milliseconds.

A ``CompiledDesign`` is the product of multi-second CMVM solves, but its
execution pipeline is fully determined by plain integer data: the packed
DAIS program of every unique CMVM (``DAISProgram.to_arrays``), the
bias / pre-shift / requant arrays of each step, the step topology, and
the quantization metadata.  ``save_design`` persists exactly that — a
single no-pickle ``design.npz`` plus a human-readable ``manifest.json``
(format ``da4ml-design`` v1) — and ``load_design`` rebuilds a design
whose ``forward_int`` is bit-identical to the one that was saved, with
**zero** solver calls (``solver_stats["n_solves"] == 0``).

The loader reconstructs the instruction tables with ``compile_tables``
(deterministic) and the executable steps through the same
``repro.nn.compiler.build_steps`` builder the compiler itself uses, so
there is no separate "deserialized" execution path to drift.  Rebuilt
tables carry the same content digest as the originals, so a process that
already jitted a design reuses its XLA executables for the loaded copy.

Layout of ``<path>/``:

    manifest.json   format/version, in/out shapes, quantization, step
                    topology (arrays referenced by npz key), per-layer
                    resource reports, compile-time solver stats.
    design.npz      all integer arrays (programs, biases, shifts,
                    requant deltas, output qints), int64, no pickle.

Crash safety: ``save_design`` commits in order — arrays first, manifest
last — with each file written to a temp name, fsync'd, atomically
renamed into place, and the directory fsync'd after each rename.  The
manifest (which binds the arrays by content digest) is therefore the
commit record: a crash at any point leaves either the previous complete
artifact or a stray temp file, never a manifest pointing at missing or
torn arrays.  ``load_design`` maps every torn/truncated/mixed-generation
shape to :class:`ArtifactCorruptError` (a ``ValueError``) and can
optionally quarantine the corrupt directory aside so a cold-start sweep
over an artifact store survives one bad entry.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
import zipfile
from dataclasses import asdict
from pathlib import Path

import numpy as np

from ..chaos import fault_point, io_fault

from ..core.dais import DAISProgram, qints_from_array, qints_to_array
from ..flow.config import CompileConfig
from ..kernels.adder_graph import compile_tables
from ..nn.compiler import CompiledDesign, LayerReport, StepSpec, build_steps
from ..nn.quant import QuantConfig

FORMAT_NAME = "da4ml-design"
FORMAT_VERSION = 1
_PROGRAM_KEYS = ("rows", "outputs", "n_inputs")


class ArtifactCorruptError(ValueError):
    """The artifact directory exists but its contents are damaged —
    truncated/torn ``design.npz``, unparsable ``manifest.json``, a
    manifest whose content digest does not match the arrays
    (mixed-generation), or arrays missing keys the manifest references.

    Subclasses ``ValueError`` so callers that guarded loads with the
    historical ``except ValueError`` keep working.  When
    ``load_design(..., on_corrupt="quarantine")`` moved the directory
    aside, the destination is recorded on ``quarantined_to``.
    """

    def __init__(self, message: str, quarantined_to: Path | None = None):
        super().__init__(message)
        self.quarantined_to = quarantined_to


def _fsync_replace(tmp: Path, dst: Path) -> None:
    """fsync ``tmp``, rename it over ``dst``, fsync the directory.

    The file fsync makes the rename publish *complete* contents; the
    directory fsync makes the rename itself durable, so a crash cannot
    reorder "manifest committed" before "arrays durable"."""
    with open(tmp, "rb") as fh:
        os.fsync(fh.fileno())
    tmp.replace(dst)
    dfd = os.open(dst.parent, os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def _arrays_digest(arrays: dict[str, np.ndarray]) -> str:
    """Content hash binding manifest.json to its design.npz.

    The two files are replaced individually; a crash between the two
    replaces could pair a stale manifest with fresh arrays (the npz key
    names repeat across saves, so the mix would load without error).
    The manifest stores this digest and the loader recomputes it, so a
    mixed-generation artifact fails loudly instead of mis-executing."""
    h = hashlib.sha256(b"da4ml-design-arrays-v1")
    for k in sorted(arrays):
        h.update(k.encode())
        h.update(np.ascontiguousarray(arrays[k]).tobytes())
    return h.hexdigest()


def _sanitize(obj):
    """Keep only JSON-serializable scalars (recursively) from a stats dict."""
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            s = _sanitize(v)
            if s is not None:
                out[str(k)] = s
        return out
    if isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return None


def save_design(design: CompiledDesign, path: str | Path) -> Path:
    """Persist a compiled design to ``path`` (a directory, created).

    Raises ``ValueError`` if any of the design's DAIS programs could not
    be packed into int64 arrays (interval endpoints beyond int64 — not
    reachable for realistic quantized networks).
    """
    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    arrays: dict[str, np.ndarray] = {}

    for i, parr in enumerate(design.programs):
        if parr is None:
            raise ValueError(
                f"program {i} is not int64-serializable; design cannot be saved"
            )
        for k in _PROGRAM_KEYS:
            arrays[f"prog{i}_{k}"] = parr[k]

    counter = iter(range(1 << 30))

    def spec_json(s: StepSpec) -> dict:
        entry: dict = {"kind": s.kind, "params": s.params, "table": s.table}
        refs: dict[str, str] = {}
        for name, arr in s.arrays.items():
            key = f"step{next(counter)}_{name}"
            arrays[key] = np.asarray(arr, np.int64)
            refs[name] = key
        entry["arrays"] = refs
        if s.body is not None:
            entry["body"] = [spec_json(b) for b in s.body]
        return entry

    steps_json = [spec_json(s) for s in design.step_specs]
    try:
        arrays["out_qints"] = qints_to_array(design.out_qints)
    except OverflowError as e:
        raise ValueError(f"output qints not int64-serializable: {e}") from e

    assert design.in_quant is not None, "design must carry its input quantization"
    manifest = {
        "format": FORMAT_NAME,
        "version": FORMAT_VERSION,
        "arrays_sha256": _arrays_digest(arrays),
        "in_quant": {
            "bits": design.in_quant.bits,
            "int_bits": design.in_quant.int_bits,
            "signed": design.in_quant.signed,
        },
        "in_shape": list(design.in_shape),
        "out_shape": list(design.out_shape),
        "use_pallas": bool(design.use_pallas),
        "n_programs": len(design.programs),
        "steps": steps_json,
        # the typed CompileConfig that produced the design: round-trips
        # through load_design, and its content digest gives artifacts a
        # config identity (same definition the SolutionCache keys use)
        "compile_config": (
            design.config.to_dict() if design.config is not None else None
        ),
        "compile_config_digest": (
            design.config.digest() if design.config is not None else None
        ),
        "reports": [asdict(r) for r in design.reports],
        "solver_stats": _sanitize(design.solver_stats),
        # rule4ml-style per-design resource summary for downstream tooling
        "resources": {
            "total_adders": design.total_adders,
            "total_cost_bits": design.total_cost_bits,
            "total_ff_bits": design.total_ff_bits,
            "latency_cycles": design.latency_cycles,
            "max_depth": design.max_depth,
        },
    }

    # ordered commit: arrays first, manifest (the commit record) last.
    # Each step is write-temp -> fsync -> rename -> fsync-dir, so a
    # crash anywhere leaves the previous complete artifact (or a stray
    # *.tmp.* the next save overwrites), never a manifest that points
    # at missing or torn arrays.  The chaos fault points let
    # tests/test_chaos.py provoke every interleaving.
    tmp = path / "design.tmp.npz"
    fault_point("artifact.save.arrays")
    np.savez_compressed(tmp, **arrays)
    io_fault("artifact.save.truncate", tmp)  # simulated torn write
    _fsync_replace(tmp, path / "design.npz")
    fault_point("artifact.save.commit")  # crash between arrays and commit
    tmp_manifest = path / "manifest.tmp.json"
    tmp_manifest.write_text(json.dumps(manifest, indent=2, sort_keys=True))
    _fsync_replace(tmp_manifest, path / "manifest.json")
    return path


def _quarantine(path: Path) -> Path:
    """Rename a corrupt artifact directory aside (``<name>.quarantined``,
    numeric suffix on collision) so a cold-start sweep can continue past
    it while keeping the evidence for a postmortem."""
    dst = path.with_name(path.name + ".quarantined")
    n = 1
    while dst.exists():
        dst = path.with_name(f"{path.name}.quarantined.{n}")
        n += 1
    path.rename(dst)
    return dst


def _corrupt(path: Path, message: str, on_corrupt: str) -> ArtifactCorruptError:
    """Build (and, if asked, quarantine for) a corruption error."""
    quarantined_to = None
    if on_corrupt == "quarantine":
        try:
            quarantined_to = _quarantine(path)
            message += f" (quarantined to {quarantined_to})"
        except OSError:
            pass  # read-only store: still raise the typed error
    return ArtifactCorruptError(message, quarantined_to=quarantined_to)


def load_design(
    path: str | Path, verify: str = "off", on_corrupt: str = "raise"
) -> CompiledDesign:
    """Rebuild a compiled design from a ``save_design`` artifact.

    Cold-starts in milliseconds: no CMVM solves run; instruction tables
    are recompiled from the packed DAIS programs and the executable
    steps come from the shared ``build_steps`` builder, so the result is
    bit-identical to the design that was saved.

    ``verify`` ("off" default / "cheap" / "strict") runs the static
    verifier (:mod:`repro.analysis`) on the rebuilt design; error-
    severity findings raise ``DesignVerificationError``.  Default off:
    the digest check above already guards integrity, and artifact loads
    sit on serving cold-start paths.

    Damage — torn/truncated ``design.npz``, unparsable or missing-but-
    committed ``manifest.json``, digest mismatch, dangling array refs —
    raises :class:`ArtifactCorruptError` (a ``ValueError``).  A wrong
    *format* or *version* stays a plain ``ValueError``: the file is
    intact, it just isn't ours.  ``on_corrupt`` ("raise" default /
    "quarantine") controls what happens first: "quarantine" renames the
    corrupt directory to ``<name>.quarantined`` (recorded on the
    error's ``quarantined_to``) so a sweep over an artifact store can
    catch, log, and continue without tripping on the same entry twice.
    """
    if on_corrupt not in ("raise", "quarantine"):
        raise ValueError(f"on_corrupt must be 'raise' or 'quarantine', got {on_corrupt!r}")
    t0 = time.perf_counter()
    path = Path(path)
    fault_point("artifact.load.read")
    try:
        manifest_text = (path / "manifest.json").read_text()
    except FileNotFoundError:
        if (path / "design.npz").exists():
            # arrays landed but the commit record didn't: an interrupted
            # save, indistinguishable from corruption for the loader
            raise _corrupt(
                path,
                f"{path}: design.npz present but manifest.json missing "
                "(interrupted save; artifact never committed)",
                on_corrupt,
            ) from None
        raise
    try:
        manifest = json.loads(manifest_text)
    except json.JSONDecodeError as e:
        raise _corrupt(
            path, f"{path}: manifest.json is not valid JSON ({e})", on_corrupt
        ) from e
    if not isinstance(manifest, dict) or manifest.get("format") != FORMAT_NAME:
        raise ValueError(f"{path}: not a {FORMAT_NAME} artifact")
    if manifest.get("version") != FORMAT_VERSION:
        raise ValueError(
            f"{path}: unsupported artifact version {manifest.get('version')}"
        )
    try:
        with np.load(path / "design.npz", allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files}
    except FileNotFoundError:
        raise _corrupt(
            path,
            f"{path}: manifest.json present but design.npz missing",
            on_corrupt,
        ) from None
    except (zipfile.BadZipFile, OSError, ValueError, EOFError) as e:
        raise _corrupt(
            path,
            f"{path}: design.npz is torn or truncated ({e})",
            on_corrupt,
        ) from e
    want = manifest.get("arrays_sha256")
    if want is not None and _arrays_digest(arrays) != want:
        raise _corrupt(
            path,
            f"{path}: design.npz does not match manifest.json "
            "(corrupt or mixed-generation artifact)",
            on_corrupt,
        )

    try:
        return _rebuild(path, manifest, arrays, verify, t0)
    except KeyError as e:
        # manifest references an array key the npz does not carry
        raise _corrupt(
            path,
            f"{path}: manifest references missing array {e} "
            "(corrupt or mixed-generation artifact)",
            on_corrupt,
        ) from e


def _rebuild(
    path: Path, manifest: dict, arrays: dict, verify: str, t0: float
) -> CompiledDesign:
    programs = []
    tables = []
    for i in range(manifest["n_programs"]):
        parr = {k: arrays[f"prog{i}_{k}"] for k in _PROGRAM_KEYS}
        programs.append(parr)
        tables.append(compile_tables(DAISProgram.from_arrays(parr)))

    def spec_from(entry: dict) -> StepSpec:
        return StepSpec(
            entry["kind"],
            params=entry["params"],
            arrays={name: arrays[key] for name, key in entry["arrays"].items()},
            table=entry.get("table", -1),
            body=(
                [spec_from(b) for b in entry["body"]] if "body" in entry else None
            ),
        )

    specs = [spec_from(e) for e in manifest["steps"]]
    iq = manifest["in_quant"]
    use_pallas = bool(manifest.get("use_pallas", False))
    cfg_dict = manifest.get("compile_config")
    config = CompileConfig.from_dict(cfg_dict) if cfg_dict is not None else None
    design = CompiledDesign(
        in_quant=QuantConfig(iq["bits"], iq["int_bits"], iq["signed"]),
        in_shape=tuple(manifest["in_shape"]),
        out_shape=tuple(manifest["out_shape"]),
        out_qints=qints_from_array(arrays["out_qints"]),
        reports=[LayerReport(**r) for r in manifest["reports"]],
        step_specs=specs,
        tables=tables,
        programs=programs,
        use_pallas=use_pallas,
        config=config,
    )
    design.steps = build_steps(specs, tables, use_pallas, programs)
    design.solver_stats = {
        "n_solves": 0,
        "n_cache_hits": 0,
        "n_pool_solves": 0,
        "pool_fallback": "loaded_from_artifact",
        "solver_time_s": 0.0,
        "loaded_from_artifact": True,
        "load_s": time.perf_counter() - t0,
        "compile_solver_stats": manifest.get("solver_stats", {}),
    }
    if verify != "off":
        from ..analysis import DesignVerificationError, verify_design

        vrep = verify_design(design, tier=verify)
        design.solver_stats["verify"] = {
            "tier": verify,
            "ok": vrep.ok,
            "n_errors": len(vrep.errors),
            "n_warnings": len(vrep.warnings),
            "pass_wall_s": {
                k: v for k, v in vrep.pass_wall_s.items() if isinstance(v, float)
            },
        }
        if not vrep.ok:
            raise DesignVerificationError(vrep, context=f"artifact {path}")
    return design
