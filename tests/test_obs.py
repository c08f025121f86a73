"""Telemetry layer: the tracer must be an exact no-op when disabled and
schema-valid Perfetto JSON when enabled, the sharded metrics registry
must merge concurrent single-writer shards without losing a count, the
flight recorder's ring/slowest-K bookkeeping must be exact through
wraparound, and the instrumented pipeline (solver spans + per-layer
compile stats + serve flight records + Prometheus exposition) must
surface real numbers without perturbing results."""

import json
import threading
import time

import numpy as np
import pytest

import jax

from repro.core import SolutionCache, solve_cmvm
from repro.flow import CompileConfig, Deployment, ServeConfig, SolverConfig
from repro.nn import QDense, QuantConfig, compile_model, init_params
from repro.obs import flight as flight_mod
from repro.obs import solvelog, trace
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import (
    Histogram,
    MetricsRegistry,
    render_prometheus,
)
from repro.runtime.metrics import LatencyRecorder


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with tracing off and empty rings."""
    was = trace.enabled()
    trace.set_enabled(False)
    trace.reset()
    yield
    trace.set_enabled(was)
    trace.reset()


# ---------------------------------------------------------------- trace


def test_disabled_span_is_shared_noop():
    assert not trace.enabled()
    s1 = trace.span("a", k=1)
    s2 = trace.span("b")
    assert s1 is s2  # module singleton: zero allocation on the hot path
    with s1:
        pass
    trace.instant("tick")
    assert trace.n_events() == 0


def test_span_with_profiler_sink_and_no_session_is_shared_noop():
    import repro.runtime.engine  # noqa: F401  (registers the profiler sink)

    assert trace._profiler is jax.profiler.TraceAnnotation
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert trace.span("x", k=1) is trace._NOOP


def _host_events(log_dir):
    """(thread line, name, start_ns, end_ns, stats) of every event in the
    profile's ``/host:CPU`` plane; a line is one thread (several may share
    a name, so it is keyed by position)."""
    import glob

    from jax.profiler import ProfileData

    out = []
    for f in glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(f).planes:
            if plane.name == "/host:CPU":
                for k, line in enumerate(plane.lines):
                    for e in line.events:
                        out.append(((f, k), e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_span_writes_ring_and_profiler_under_a_session(tmp_path):
    import repro.runtime.engine  # noqa: F401  (registers the profiler sink)

    trace.set_enabled(True)
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("obs.outer", k=1):
            with trace.span("obs.inner"):
                pass
    ring = {e["name"]: e for e in trace.export()["traceEvents"] if e["ph"] == "X"}
    assert ring["obs.outer"]["args"] == {"k": 1} and "obs.inner" in ring
    prof = {name: (lo, hi, st) for _, name, lo, hi, st in _host_events(tmp_path)}
    assert prof["obs.outer"][2] == {"k": 1}
    assert prof["obs.outer"][0] <= prof["obs.inner"][0] <= prof["obs.inner"][1] <= prof["obs.outer"][1]


def test_disabled_tracing_is_bit_exact_on_solver():
    mat = np.random.default_rng(7).integers(-64, 64, size=(12, 12))
    cfg = SolverConfig(dc=2, engine="arena")
    ref = solve_cmvm(mat, config=cfg)
    assert trace.n_events() == 0
    trace.set_enabled(True)
    traced = solve_cmvm(mat, config=cfg)
    assert trace.n_events() > 0
    assert (traced.n_adders, traced.cost_bits) == (ref.n_adders, ref.cost_bits)


def test_span_records_nesting_and_args():
    trace.set_enabled(True)
    with trace.span("outer", phase="x"):
        with trace.span("inner"):
            pass
        trace.instant("mark", n=3)
    doc = trace.export()
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert set(xs) == {"outer", "inner"}
    assert xs["outer"]["args"] == {"phase": "x"}
    assert xs["outer"]["dur"] >= xs["inner"]["dur"] >= 0
    assert [e["name"] for e in inst] == ["mark"]


def test_trace_ring_wraparound_counts_dropped():
    trace.set_enabled(True)
    results = {}

    def work():
        # fresh thread => fresh buffer created at the tiny capacity
        for i in range(10):
            with trace.span(f"s{i}"):
                pass
        b = trace._buf()
        results["names"] = [ev[0] for ev in b.iter_events()]
        results["n_dropped"] = b.n_dropped

    old_cap = trace._capacity
    trace.set_capacity(4)
    try:
        t = threading.Thread(target=work)
        t.start()
        t.join()
    finally:
        trace.set_capacity(old_cap)
    # ring keeps the newest 4 of 10, oldest-first, and counts the rest
    assert results["names"] == ["s6", "s7", "s8", "s9"]
    assert results["n_dropped"] == 6
    doc = trace.export()
    assert doc["otherData"]["n_dropped"] >= 6


def test_export_is_valid_chrome_trace_json(tmp_path):
    trace.set_enabled(True)

    def work():
        with trace.span("pool.work", idx=1):
            pass

    t = threading.Thread(target=work, name="worker-0")
    t.start()
    t.join()
    with trace.span("main.work"):
        pass
    path = tmp_path / "trace.json"
    doc = trace.export(str(path))
    reloaded = json.loads(path.read_text())
    assert reloaded == doc
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"pool.work", "main.work"}
    for e in xs:
        for key in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            assert key in e
        assert e["ts"] >= 0 and e["dur"] >= 0
    # spans on two distinct threads, each with thread_name metadata
    assert len({e["tid"] for e in xs}) == 2
    assert {e["tid"] for e in xs} <= {e["tid"] for e in ms}
    assert any(e["args"]["name"] == "worker-0" for e in ms)


# -------------------------------------------------------------- metrics


def test_registry_empty_snapshot_and_prometheus():
    reg = MetricsRegistry()
    snap = reg.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert reg.to_prometheus() == "\n"


def test_registry_concurrent_writers_sum_exactly():
    reg = MetricsRegistry()
    n_threads, n_incs = 8, 500

    def work(i):
        for k in range(n_incs):
            reg.inc("ops_total", kind="w")
            reg.observe("lat_us", float(k % 100))
        reg.set_gauge("depth", i, shard=str(i))

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]['ops_total{kind="w"}'] == n_threads * n_incs
    assert snap["histograms"]["lat_us"]["count"] == n_threads * n_incs
    for i in range(n_threads):
        assert snap["gauges"][f'depth{{shard="{i}"}}'] == i


def test_gauge_last_write_wins_across_shards():
    reg = MetricsRegistry()
    reg.set_gauge("q", 1.0)

    def late_writer():
        reg.set_gauge("q", 42.0)

    t = threading.Thread(target=late_writer)
    t.start()
    t.join()
    assert reg.snapshot()["gauges"]["q"] == 42.0


def test_histogram_merge_and_percentiles():
    a, b = Histogram(), Histogram()
    for v in (5.0, 50.0, 500.0):
        a.observe(v)
    b.observe(5_000.0)
    m = Histogram.merged([a, b])
    assert (m.n, m.sum) == (4, 5555.0)
    assert Histogram.merged([]).n == 0  # merged over nothing: empty hist
    snap = m.snapshot()
    assert snap["buckets"][float("inf")] == 4
    # cumulative monotonicity
    cum = list(snap["buckets"].values())
    assert cum == sorted(cum)
    assert m.percentile(0) <= m.percentile(50) <= m.percentile(100)
    with pytest.raises(ValueError):
        a.merge_from(Histogram(bounds=(1.0, 2.0)))


def test_prometheus_histogram_exposition_shape():
    h = Histogram(bounds=(10.0, 100.0))
    for v in (5.0, 50.0, 500.0):
        h.observe(v)
    text = render_prometheus(
        [("stage_us", "histogram", "per-stage µs", [({"stage": "pad"}, h)])]
    )
    lines = text.strip().splitlines()
    assert "# TYPE stage_us histogram" in lines
    assert 'stage_us_bucket{stage="pad",le="10"} 1' in lines
    assert 'stage_us_bucket{stage="pad",le="100"} 2' in lines
    assert 'stage_us_bucket{stage="pad",le="+Inf"} 3' in lines
    assert 'stage_us_count{stage="pad"} 3' in lines


# --------------------------------------------------------------- flight


def test_flight_ring_wraparound_and_slowest_k():
    fr = FlightRecorder(capacity=8, slow_k=3)
    for i in range(20):
        # latency pattern puts the slowest three at i = 17, 18, 19 * 10
        fr.record(i, shard=0, bucket=16, batch_size=4,
                  lat_us=float(i * 10), stages_us=(1, 2, 3, 4, float(i)))
    snap = fr.snapshot()
    assert snap["n_records"] == 20
    assert snap["n_evicted"] == 12
    recent = fr.recent()
    assert [r["trace_id"] for r in recent] == list(range(12, 20))
    assert [r["lat_us"] for r in snap["slowest"]] == [190.0, 180.0, 170.0]
    r = snap["slowest"][0]
    assert r["stages_us"] == {
        "queue_wait": 1, "batch_form": 2, "pad": 3, "dispatch": 4,
        "copy_out": 19.0,
    }
    assert set(r["stages_us"]) == set(flight_mod.STAGES)


def test_flight_merged_over_empty_and_mixed():
    assert FlightRecorder.merged([]) == {
        "n_records": 0, "capacity": 0, "n_evicted": 0, "slowest": [],
        "n_events": 0, "events": [],
    }
    empty = FlightRecorder(capacity=4, slow_k=2)
    busy = FlightRecorder(capacity=4, slow_k=2)
    busy.record(1, 0, 16, 1, 100.0, (1, 1, 1, 1, 1))
    busy.record(2, 0, 16, 1, 900.0, (2, 2, 2, 2, 2))
    m = FlightRecorder.merged([empty, busy])
    assert m["n_records"] == 2
    assert [r["trace_id"] for r in m["slowest"]] == [2, 1]


def test_flight_merged_interleaves_shards():
    a = FlightRecorder(capacity=16, slow_k=2)
    b = FlightRecorder(capacity=16, slow_k=2)
    a.record(10, 0, 16, 1, 50.0, (0, 0, 0, 0, 0))
    b.record(20, 1, 16, 1, 70.0, (0, 0, 0, 0, 0))
    a.record(11, 0, 16, 1, 60.0, (0, 0, 0, 0, 0))
    m = FlightRecorder.merged([a, b])
    assert [r["trace_id"] for r in m["slowest"]] == [20, 11]
    assert {r["shard"] for r in m["slowest"]} == {1, 0}


# ------------------------------------------------------- reservoir fix


def test_latency_reservoir_is_deterministic_and_uniformish():
    r1 = LatencyRecorder(max_samples=100, seed=3)
    r2 = LatencyRecorder(max_samples=100, seed=3)
    vals = [float(i) for i in range(1000)]
    for v in vals:
        r1.record(v, now=0.0)
    r2.record_many(vals, now=0.0)
    assert r1.n_total == r2.n_total == 1000
    assert r1.n_sampled_out == r2.n_sampled_out == 900
    # same seed, same arrival order => identical reservoirs however fed
    assert r1._lat == r2._lat
    # Algorithm R must not freeze on the first max_samples observations
    assert max(r1._lat) >= 100.0
    snap = r1.snapshot()
    assert snap["n_sampled_out"] == 900
    assert snap["n_latency_samples"] == 100
    r1.reset()
    assert (r1.n_total, r1.n_sampled_out, r1._lat) == (0, 0, [])


def test_latency_reservoir_seed_changes_sample():
    a = LatencyRecorder(max_samples=50, seed=0)
    b = LatencyRecorder(max_samples=50, seed=1)
    for v in range(500):
        a.record(float(v), now=0.0)
        b.record(float(v), now=0.0)
    assert a._lat != b._lat


# ------------------------------------------- instrumented pipeline (jax)


@pytest.fixture(scope="module")
def design():
    wq = QuantConfig(6, 2, signed=True)
    model = (QDense(8, wq), QDense(4, wq))
    params, _ = init_params(jax.random.PRNGKey(0), model, (8,))
    return compile_model(
        model, params, (8,), QuantConfig(8, 4, signed=True),
        config=CompileConfig(solver=SolverConfig(dc=2)),
    )


def test_per_layer_solver_stats(design):
    per_layer = design.solver_stats["per_layer"]
    assert sorted(per_layer) == ["dense0", "dense1"]
    for st in per_layer.values():
        assert st["cache_hit"] is False
        assert st["solve_wall_s"] >= 0.0
        assert st["adders"] > 0 and st["cost_bits"] > 0
    assert per_layer["dense0"]["shape"] == "8x8"
    assert per_layer["dense1"]["shape"] == "8x4"


def test_per_layer_cache_hits_with_shared_cache():
    cache = SolutionCache()
    wq = QuantConfig(6, 2, signed=True)
    model = (QDense(8, wq),)
    params, _ = init_params(jax.random.PRNGKey(1), model, (8,))
    in_q = QuantConfig(8, 4, signed=True)
    cfg = CompileConfig(solver=SolverConfig(dc=2), cache=cache)
    first = compile_model(model, params, (8,), in_q, config=cfg)
    second = compile_model(model, params, (8,), in_q, config=cfg)
    assert first.solver_stats["per_layer"]["dense0"]["cache_hit"] is False
    assert second.solver_stats["per_layer"]["dense0"]["cache_hit"] is True


def test_solvelog_captures_structured_records(tmp_path):
    path = tmp_path / "solves.jsonl"
    solvelog.reset()
    old = solvelog.get_path()
    solvelog.set_path(str(path))
    try:
        mat = np.random.default_rng(11).integers(-64, 64, size=(10, 10))
        sol = solve_cmvm(mat, config=SolverConfig(dc=2, engine="arena"))
    finally:
        solvelog.set_path(old)
    recs = [r for r in solvelog.records() if r.get("d_in") == 10]
    assert recs, "solve record missing from ring"
    rec = recs[-1]
    assert rec["adders"] == sol.n_adders
    assert rec["cost_bits"] == sol.cost_bits
    assert rec["cache_hit"] is False
    on_disk = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert any(r["d_in"] == 10 and r["adders"] == sol.n_adders for r in on_disk)


def test_engine_stats_carry_flight_and_metrics_text(design):
    rng = np.random.default_rng(2)
    xs = [
        np.asarray(rng.integers(-8, 8, size=(8,)), np.int32) for _ in range(32)
    ]
    with Deployment(ServeConfig(max_batch=8, max_wait_us=100.0, shards=2)) as dep:
        dep.register("m", design)
        dep.warmup("m")
        for f in [dep.submit("m", x) for x in xs]:
            f.result(30)
        stats = dep.stats("m")
        text = dep.metrics_text()
    flight = stats["flight"]
    assert flight["n_records"] >= len(xs)
    assert flight["slowest"], "tail sample must pin at least one request"
    for rec in flight["slowest"]:
        assert set(rec["stages_us"]) == set(flight_mod.STAGES)
        assert rec["lat_us"] > 0
    # trace ids unique across shards (shard index in the high bits)
    tids = [r["trace_id"] for r in flight["slowest"]]
    assert len(tids) == len(set(tids))
    # Prometheus text: every sample line parses, serve families present
    samples = [
        ln for ln in text.splitlines() if ln.strip() and not ln.startswith("#")
    ]
    import re

    pat = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$')
    assert samples and all(pat.match(ln) for ln in samples)
    assert any(
        ln.startswith('serve_requests_total{model="m@v1"}') for ln in samples
    )
    for family in ("serve_batches_total", "serve_stage_us_bucket",
                   "serve_queue_depth"):
        assert family in text


def test_engine_stage_spans_land_in_the_profiler_host_plane(design, tmp_path):
    """Under a profiler session (ring off), each batch writes
    ``serve.batch`` with its args and the four stages nested in it, plus
    ``serve.idle`` and ``serve.batch_form``; a flight record's trace id
    falls in the id range of one batch of its shard."""
    xs = np.random.default_rng(3).integers(-8, 8, size=(40, 8)).astype(np.int32)
    with Deployment(ServeConfig(max_batch=8, max_wait_us=100.0, shards=2)) as dep:
        dep.register("m", design, warmup=True)
        with jax.profiler.trace(str(tmp_path)):
            for f in [dep.submit("m", x) for x in xs]:
                f.result(30)
            # let the last batch finish its bookkeeping inside the session
            for _ in range(500):
                ps = dep.stats("m")
                if ps["per_stage"]["observe"]["count"] == ps["n_batches"]:
                    break
                time.sleep(0.01)
            time.sleep(0.05)
        stats = dep.stats("m")
    events = _host_events(tmp_path)
    batches = [e for e in events if e[1] == "serve.batch"]
    assert batches
    for line, _, lo, hi, st in batches:
        assert set(st) == {"shard", "seq", "bucket", "n", "first_tid", "last_tid"}
        assert st["last_tid"] - st["first_tid"] == st["n"] - 1
        inner = {e[1] for e in events if e[0] == line and lo <= e[2] and e[3] <= hi}
        assert {"serve.pad", "serve.dispatch", "serve.copy_out", "serve.observe"} <= inner
    assert sum(e[4]["n"] for e in batches) == len(xs)
    names = {e[1] for e in events}
    assert {"serve.idle", "serve.batch_form"} <= names
    for rec in stats["flight"]["slowest"]:
        assert any(
            st["shard"] == rec["shard"] and st["first_tid"] <= rec["trace_id"] <= st["last_tid"]
            for *_, st in batches
        )
