"""Run one cell once: set up, measure the window, check, build the line.

``run_cell`` is the whole of a run.  Set-up is everything from the
process's start until the window opens: imports, the chip's start, the
design's artifact (solved only on a checkout's first run), the compile
cache, the shapes the cell's traffic uses, the events, and for the
online mix a short pre-roll of the same traffic.  The window then runs
for ``seconds``.  After it the device's peak memory is read, the
program's state is dropped, and every answer of the window is compared
with the reference.
"""

from __future__ import annotations

import gc
import importlib.util
import shutil
import time
from contextlib import nullcontext
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from . import check, loadgen, network, reference
from .cell import ROOT, Cell, load_cell
from .peaks import peak
from .trace import Capture, Summary


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclass
class Record:
    """What one run measured; the per-layer readers read it."""

    cell: Cell
    device_kind: str
    seconds: float  # the window, by the host clock
    events: int  # events answered in the window
    chunk: int = 0  # bulk: events per call
    in_itemsize: int = 1  # bytes of one input value as sent
    batches: int = 0  # online: batches the engine dispatched
    stage_s: dict | None = None  # online: engine stage -> (seconds, count)
    lag_s: np.ndarray | None = None  # online: send time minus due time
    latency_s: np.ndarray | None = None  # online: due time to answer, a failure the whole run
    trace: Summary | None = None  # --trace 1 only

    def peak(self) -> dict:
        return peak(self.device_kind)


def rng_of(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed % 2**64)


def input_dtype(config: dict) -> np.dtype:
    """The narrowest integer dtype that holds the input grid."""
    lo, hi, _ = reference.grid(config["in_quant"])
    for dt in (np.int8, np.int16, np.int32):
        if np.iinfo(dt).min <= lo and hi <= np.iinfo(dt).max:
            return np.dtype(dt)
    raise ValueError("input grid wider than int32")


def events(config: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` events drawn uniformly from the input grid."""
    lo, hi, _ = reference.grid(config["in_quant"])
    dt = input_dtype(config)
    return rng.integers(lo, hi, size=(n, *config["in_shape"]), endpoint=True, dtype=dt)


def _stage_delta(before: dict, after: dict) -> dict:
    return {
        k: ((after[k]["total_ms"] - before[k]["total_ms"]) * 1e-3,
            after[k]["count"] - before[k]["count"])
        for k in after
    }


def draw_online(cell: Cell, seconds: float, rng: np.random.Generator):
    """The online mix's inputs, in the order a run draws them: the pool
    of events, the window's due times and the event each request
    carries, then the same two for the pre-roll."""
    t = cell.traffic
    pool = events(cell.config, t["pool_events"], rng)
    due = loadgen.schedule(t["rate_per_s"], seconds, rng)
    pick = rng.integers(0, len(pool), size=len(due))
    pre_due = loadgen.schedule(t["rate_per_s"], t["preroll_s"], rng)
    pre_pick = rng.integers(0, len(pool), size=len(pre_due))
    return pool, due, pick, pre_due, pre_pick


def draw_bulk(cell: Cell, rng: np.random.Generator):
    """The bulk mix's inputs: a pool of events and the pool offset of
    each call's chunk."""
    chunk = int(cell.traffic["chunk_events"])
    span = chunk * int(cell.traffic["pool_chunks"])
    pool = events(cell.config, span + chunk, rng)
    return pool, rng.integers(0, span + 1, size=1 << 16)


def _online(cell, design, rng, seconds, capture):
    """The open loop through ``Deployment.submit``.  Returns (record
    fields, the window's run, pool, picked event of each request,
    clock reading when the window opened)."""
    from repro.flow import Flow, ServeConfig

    t = cell.traffic
    name = cell.config["name"]
    pool, due, pick, pre_due, pre_pick = draw_online(cell, seconds, rng)
    serve = dict(t["serve"], buckets=tuple(t["serve"]["buckets"]))
    out_shape = tuple(design.out_shape)
    dep = Flow.serve(ServeConfig(**serve))
    try:
        dep.register(name, design, warmup=True)

        def submit(x):
            return dep.submit(name, x)

        loadgen.open_loop(submit, lambda i: pool[pre_pick[i]], pre_due, out_shape)
        gc.collect()
        gc.freeze()  # set-up's objects are long-lived: keep them out of collections
        s0 = dep.stats(name)
        with capture:
            t_open = time.perf_counter()
            run = loadgen.open_loop(submit, lambda i: pool[pick[i]], due, out_shape)
        s1 = dep.stats(name)
    finally:
        dep.shutdown()
    fields = {
        "seconds": run.seconds,
        "events": int((~run.failed & ~np.isnan(run.done)).sum()),
        "batches": s1["n_batches"] - s0["n_batches"],
        "stage_s": _stage_delta(s0["per_stage"], s1["per_stage"]),
        "lag_s": run.lag_s,
        # a failed request missed every limit: it counts as waiting the whole run
        "latency_s": np.minimum(run.latency_s, run.seconds),
    }
    fallback = s1["n_fallback_batches"] - s0["n_fallback_batches"]
    if fallback:
        raise RuntimeError(f"{fallback} batches took the fallback; the cell serves without one")
    return fields, run, pool, pick, t_open


def _bulk(cell, design, rng, seconds, capture):
    """The closed loop over the jitted ``forward_int``."""
    import jax

    chunk = int(cell.traffic["chunk_events"])
    pool, offsets = draw_bulk(cell, rng)
    fn = jax.jit(design.forward_int)
    for _ in range(2):  # the first call compiles or loads the chunk's shape
        np.asarray(fn(pool[:chunk]))
    gc.collect()
    gc.freeze()
    with capture:
        t_open = time.perf_counter()
        run = loadgen.closed_loop(fn, pool, chunk, offsets, seconds)
    fields = {
        "seconds": run.seconds,
        "events": run.events,
        "chunk": chunk,
        "in_itemsize": pool.dtype.itemsize,
    }
    return fields, run, pool, t_open


def _read_metric(root: Path, name: str, rec: Record):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def run_cell(name: str, seed: int, seconds: float, trace: bool, root: Path = ROOT,
             t_process: float | None = None, require_tpu: bool = True) -> dict:
    """One run of cell ``name``; returns its result line as a dict.

    ``require_tpu=False`` lets a run go on without a chip, for the tests;
    device numbers are then missing, never stood in for."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = load_cell(name, root)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(
            f"cell {name!r} needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)"
        )
    dev = devices[0]
    design, _ = network.load_design(cell.config, root)
    rng = rng_of(seed)
    trace_dir = root / "bench" / ".cache" / "trace" / name
    shutil.rmtree(trace_dir, ignore_errors=True)
    capture = Capture(str(trace_dir)) if trace else nullcontext()

    kind = cell.traffic["kind"]
    if kind == "open_poisson":
        fields, run, pool, pick, t_open = _online(cell, design, rng, seconds, capture)
    elif kind == "closed_chunks":
        fields, run, pool, t_open = _bulk(cell, design, rng, seconds, capture)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    setup_s = t_open - t_process
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    summary = capture.summary() if trace else None
    shutil.rmtree(trace_dir, ignore_errors=True)

    scale = check.output_scale(design)
    del design
    gc.collect()
    want = reference.forward(
        cell.config, network.make_params(cell.config, root), pool, root=root
    )
    if kind == "open_poisson":
        ok = ~run.failed & ~np.isnan(run.done)
        attempted = len(run.due)
        failed = attempted - int(ok.sum())
        mism = check.mismatched(run.outputs[ok], scale, want[pick[ok]])
        e2e = {"p50_ms": float(np.percentile(fields["latency_s"], 50)) * 1e3}
    else:
        attempted, failed = run.events, 0
        mism = sum(
            check.mismatched(y, scale, want[o : o + len(y)])
            for o, y in zip(run.offsets, run.outputs)
        )
        e2e = {"events_per_s": run.events / run.seconds}
    numbers = {"mismatched": mism, "failed": failed}
    e2e["setup_s"] = setup_s

    rec = Record(cell=cell, device_kind=dev.device_kind, trace=summary, **fields)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = _read_metric(root, m["name"], rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": memory_peak,
    }
    line = {
        "correct": check.verdict(numbers),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None and summary.n_devices:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps}
    line["checks"] = check.as_json(numbers)
    return line
