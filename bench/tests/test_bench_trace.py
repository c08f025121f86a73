"""The reduction from a profiler trace to busy time, idle gaps, per-executable
time and top ops: on planes made by hand, and on a recorded TPU trace."""

from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench.harness import trace

RECORDED = Path(__file__).with_name("data") / "tpu_two_calls.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _planes():
    ops = NS(name="XLA Ops", events=[
        _ev("%fusion.1 = s32[4]{0} fusion(...)", 100, 50),
        _ev("%concatenate.2 = s32[8]{0} concatenate(...)", 140, 60),  # overlaps: busy 100-200
        _ev("%fusion.1 = s32[4]{0} fusion(...)", 500, 100),  # busy 500-600
    ])
    dma = NS(name="Async XLA Ops", events=[_ev("%copy-start.3 = (s32[4]) copy-start(...)", 590, 30)])
    modules = NS(name="XLA Modules", events=[
        _ev("jit_forward_int(123)", 100, 100), _ev("jit_forward_int(123)", 500, 120),
    ])
    device = NS(name="/device:TPU:0", lines=[modules, ops, dma])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev(trace.WINDOW_SPAN, 0, 1000),
        _ev("np.asarray(jax.Array)", 180, 340),  # covers the gap 200-500
        _ev("Linearize", 210, 280),  # shorter, covers nearly as much of it
        _ev("DevicePut", 620, 300),  # covers the gap 620-920
    ])])
    other = NS(name="/host:metadata", lines=[])
    return [other, device, host]


def test_union_merges_overlapping_and_touching_intervals():
    iv = np.array([[5, 7], [0, 2], [1, 3], [3, 4], [10, 11]], float)
    np.testing.assert_array_equal(trace.union(iv), [[0, 4], [5, 7], [10, 11]])
    assert trace.union(np.zeros((0, 2))).shape == (0, 2)


def test_reduce_hand_made_planes():
    planes = _planes()
    window = trace.window_of(planes, (0.0, 1.0))
    assert window == (0.0, 1000.0)
    s = trace.reduce_planes(planes, window)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(220e-9)  # 100-200 and 500-620
    assert s.module_s == {"jit_forward_int": pytest.approx(220e-9)}
    assert s.module_n == {"jit_forward_int": 2}
    assert [k for k, _ in s.top_ops] == ["fusion.1", "concatenate.2", "copy-start.3"]
    assert s.top_ops[0][1] == pytest.approx(150e-9)
    gaps = {name: sec for name, sec in s.idle_gaps}
    assert [round(sec * 1e9) for _, sec in s.idle_gaps] == [380, 300, 100]
    assert gaps["DevicePut"] == pytest.approx(380e-9)  # 620-1000
    assert gaps["Linearize"] == pytest.approx(300e-9)  # 200-500
    assert gaps["no host span"] == pytest.approx(100e-9)  # 0-100
    assert s.busy_s + sum(g for _, g in s.idle_gaps) == pytest.approx(s.window_s)


def test_no_device_plane_reports_nothing_of_the_device():
    planes = [p for p in _planes() if not p.name.startswith(trace.DEVICE_PREFIX)]
    s = trace.reduce_planes(planes, (0.0, 1000.0))
    assert s.n_devices == 0 and s.busy_s == 0.0 and not s.top_ops and not s.idle_gaps


def test_reduce_recorded_tpu_trace():
    """Two calls of jit(forward_int) of jet_tagger at batch 64 on one
    TPU v5 lite, traced through ``trace.Capture``."""
    jax = pytest.importorskip("jax")
    data = jax.profiler.ProfileData.from_file(str(RECORDED))
    planes = list(data.planes)  # an iterator, read twice below
    window = trace.window_of(planes, (0.0, 0.0))
    assert window[1] > window[0] > 0
    s = trace.reduce_planes(planes, window)
    assert s.n_devices == 1
    assert s.module_n == {"jit_forward_int": 2}
    assert 0 < s.busy_s <= s.module_s["jit_forward_int"] * 1.01
    assert s.busy_s < s.window_s
    secs = [v for _, v in s.top_ops]
    assert len(secs) == trace.TOP and secs == sorted(secs, reverse=True)
    assert all(not k.startswith("%") and " = " not in k for k, _ in s.top_ops)
    assert len(s.idle_gaps) == trace.TOP
    assert all(name != "no host span" for name, _ in s.idle_gaps[:2])
