"""The reduction from a profiler trace to the numbers the readers use.

What it keys on, as the TPU runtime names it (JAX 0.9, TPU v5 lite):

- device planes are named ``/device:TPU:<n>``;
- their line ``XLA Modules`` holds one event per executable run, named
  ``<jit name>(<fingerprint>)``, e.g. ``jit_forward_int(1402...)``;
- their lines ``XLA Ops`` and ``Async XLA Ops`` hold one event per HLO
  instruction run, named by its HLO text (``%concatenate.53 = s32[...]``);
- the plane ``/host:CPU`` holds the host's spans (runtime TraceMe
  events and ``jax.profiler.TraceAnnotation``), on the same clock.

The traced window is the host span ``bench.window`` that the capture
wraps around it.  Busy time is the union of the op intervals of a device plane, averaged
over the device planes.  An idle gap is a stretch of the traced window
between two busy intervals, named by the host span that overlaps it
most (the shortest of those that overlap it nearly as much).
"""

from __future__ import annotations

import glob
import os
import re
import time
from dataclasses import dataclass, field

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class Summary:
    window_s: float  # length of the traced window, by the host clock
    n_devices: int = 0  # device planes found; 0 off a TPU
    busy_s: float = 0.0  # union of op intervals, mean over devices
    module_s: dict = field(default_factory=dict)  # executable -> device seconds
    module_n: dict = field(default_factory=dict)  # executable -> runs
    top_ops: list = field(default_factory=list)  # [[op, seconds]], longest first
    idle_gaps: list = field(default_factory=list)  # [[host span, seconds]]


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge [start, end] rows into disjoint sorted intervals."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.r_[new[1:], True])
    return np.stack([starts, ends[last]], axis=1)


def _name_gap(lo: float, hi: float, host: list) -> str:
    best, best_ov = [], 0.0
    for name, s, e in host:
        ov = min(e, hi) - max(s, lo)
        if ov > 0:
            best.append((name, ov, e - s))
            best_ov = max(best_ov, ov)
    near = [b for b in best if b[1] >= 0.5 * best_ov]
    return min(near, key=lambda b: b[2])[0] if near else "no host span"


def reduce_planes(planes, window_ns: tuple[float, float]) -> Summary:
    """Reduce profiler planes (``ProfileData(...).planes`` or objects with
    the same ``name``/``lines``/``events`` attributes) over the window
    [lo, hi] in nanoseconds."""
    lo, hi = window_ns
    out = Summary(window_s=(hi - lo) * 1e-9)
    op_time: dict = {}
    host: list = []
    busy_per_device, merged = [], None
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns < 0.5 * (hi - lo):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
            continue
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        out.n_devices += 1
        spans = []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                for e in line.events:
                    m = module_name(e.name)
                    out.module_s[m] = out.module_s.get(m, 0.0) + e.duration_ns * 1e-9
                    out.module_n[m] = out.module_n.get(m, 0) + 1
            elif line.name in OP_LINES:
                for e in line.events:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
                    k = op_name(e.name)
                    op_time[k] = op_time.get(k, 0.0) + e.duration_ns * 1e-9
        iv = np.clip(np.asarray(spans, np.float64).reshape(-1, 2), lo, hi)
        busy = union(iv)
        busy_per_device.append(float((busy[:, 1] - busy[:, 0]).sum()) * 1e-9)
        if merged is None:
            merged = busy
    if out.n_devices == 0:
        return out
    out.busy_s = float(np.mean(busy_per_device))
    out.top_ops = [[k, v] for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]]
    edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
    gaps = [(a, b) for a, b in edges if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    out.idle_gaps = [[_name_gap(a, b, host), float(b - a) * 1e-9] for a, b in gaps[:TOP]]
    return out


WINDOW_SPAN = "bench.window"


def window_of(planes, fallback_ns: tuple[float, float]) -> tuple[float, float]:
    """The traced window on the trace's clock: the host span
    ``WINDOW_SPAN`` that :class:`Capture` wraps around it."""
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        return (e.start_ns, e.start_ns + e.duration_ns)
    return fallback_ns


class Capture:
    """Profile the device while the ``with`` block runs; ``summary()``
    reduces the trace to a :class:`Summary` of that window."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.seconds = 0.0

    def __enter__(self) -> Capture:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no per-call Python events: the window is long
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        import jax

        self.seconds = time.perf_counter() - self._t0
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> Summary:
        """Reduce every plane the capture wrote: the host and each device
        may land in files of their own."""
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"), recursive=True)
        data = [ProfileData.from_file(f) for f in sorted(files)]  # planes refer into these
        planes = [p for d in data for p in d.planes]
        return reduce_planes(planes, window_of(planes, (0.0, self.seconds * 1e9)))
