"""Microbatched serving engine: results must be bit-identical to direct
``forward_int``, the registry must isolate models, backpressure /
shape validation must fail requests loudly instead of corrupting
batches, and — the serving-shutdown stress net — every Future handed
out by a submit racing ``unregister``/``shutdown``/rollout must resolve
(result or exception) within a bounded timeout, on both the
single-dispatcher and the sharded path."""

import threading
import time
from concurrent.futures import TimeoutError as FutureTimeoutError

import numpy as np
import pytest

import jax

from repro.flow import Deployment, ServeConfig
from repro.nn import QDense, QuantConfig, ReLU, compile_model, init_params
from repro.runtime import EngineClosedError, QueueFullError, ServeEngine, save_design
from repro.runtime.engine import _ModelRunner


@pytest.fixture(scope="module")
def designs():
    wq = QuantConfig(6, 2, signed=True)
    aq = QuantConfig(8, 4, signed=False)
    in_quant = QuantConfig(8, 4, signed=True)
    out = {}
    for name, units in (("a", 6), ("b", 3)):
        model = (QDense(8, wq), ReLU(aq), QDense(units, wq))
        params, _ = init_params(jax.random.PRNGKey(ord(name)), model, (8,))
        out[name] = compile_model(model, params, (8,), in_quant, dc=2)
    return out


def _samples(n, in_quant=None, d=8, seed=0):
    rng = np.random.default_rng(seed)
    q = (in_quant or QuantConfig(8, 4, signed=True)).qint
    return np.asarray(rng.integers(q.lo, q.hi + 1, size=(n, d)), np.int32)


def test_engine_results_bit_identical(designs):
    design = designs["a"]
    xs = _samples(100)
    want = np.asarray(design.forward_int(xs))
    with ServeEngine(max_batch=16, max_wait_us=100.0) as eng:
        eng.register("a", design, warmup=True)
        futs = [eng.submit("a", x) for x in xs]
        got = np.stack([f.result(30) for f in futs])
    np.testing.assert_array_equal(got, want)


def test_multi_model_registry(designs):
    xs = _samples(40)
    want = {n: np.asarray(d.forward_int(xs)) for n, d in designs.items()}
    with ServeEngine(max_batch=8, max_wait_us=100.0) as eng:
        for n, d in designs.items():
            eng.register(n, d)
        assert eng.models() == ["a", "b"]
        # interleave the two models' traffic
        futs = [(n, i, eng.submit(n, xs[i])) for i in range(40) for n in ("a", "b")]
        for n, i, f in futs:
            np.testing.assert_array_equal(f.result(30), want[n][i])
        with pytest.raises(ValueError, match="already registered"):
            eng.register("a", designs["a"])
    with pytest.raises(KeyError, match="not registered"):
        eng.submit("a", xs[0])  # shut-down engine has an empty registry


def test_register_from_artifact_path(designs, tmp_path):
    path = save_design(designs["a"], tmp_path / "a")
    xs = _samples(10, seed=5)
    with ServeEngine(max_batch=8) as eng:
        loaded = eng.register("a", path)
        assert loaded.solver_stats["n_solves"] == 0
        futs = [eng.submit("a", x) for x in xs]
        got = np.stack([f.result(30) for f in futs])
    np.testing.assert_array_equal(got, np.asarray(designs["a"].forward_int(xs)))


def test_submit_validates_shape_and_dtype(designs):
    with ServeEngine() as eng:
        eng.register("a", designs["a"])
        with pytest.raises(ValueError, match="expects one sample"):
            eng.submit("a", np.zeros((3, 8), np.int32))
        with pytest.raises(TypeError, match="integer-grid"):
            eng.submit("a", np.zeros((8,), np.float64))


def test_shutdown_never_leaves_hanging_futures(designs):
    """A request in flight when shutdown is called is either served
    during the drain or failed loudly — never left to hang until the
    client's result() timeout (even under a long batching window)."""
    eng = ServeEngine(max_batch=4, max_wait_us=500_000.0)
    eng.register("a", designs["a"], warmup=True)
    f = eng.submit("a", _samples(1, seed=6)[0])
    eng.shutdown()
    try:
        assert f.result(5).shape == (6,)
    except RuntimeError as e:
        assert "shut down" in str(e)


def test_backpressure_reject(designs):
    # tiny queue + a long batching window: the dispatcher sits in its
    # collect wait while we flood the queue, so put_nowait must overflow
    eng = ServeEngine(
        max_batch=4, queue_depth=4, max_wait_us=200_000.0, overflow="reject"
    )
    try:
        eng.register("a", designs["a"], warmup=True)
        xs = _samples(200, seed=1)
        rejected = 0
        futs = []
        for x in xs:
            try:
                futs.append(eng.submit("a", x))
            except QueueFullError:
                rejected += 1
        assert rejected > 0
        assert eng.stats("a")["n_rejected"] == rejected
        for f in futs:
            assert f.result(30).shape == (6,)
    finally:
        eng.shutdown()


def test_cancelled_future_does_not_kill_dispatcher(designs):
    """A client cancelling a queued request must not crash the
    dispatcher thread: the request is dropped and later traffic is
    still served."""
    eng = ServeEngine(max_batch=2, max_wait_us=100_000.0)
    try:
        eng.register("a", designs["a"], warmup=True)
        eng.submit("a", _samples(1, seed=3)[0]).cancel()
        xs = _samples(4, seed=4)
        futs = [eng.submit("a", x) for x in xs]
        got = np.stack([f.result(30) for f in futs])
        np.testing.assert_array_equal(got, np.asarray(designs["a"].forward_int(xs)))
    finally:
        eng.shutdown()


def test_stats_shape(designs):
    with ServeEngine(max_batch=8, max_wait_us=100.0) as eng:
        eng.register("a", designs["a"])
        warm_s = eng.warmup("a")
        assert warm_s > 0
        for f in [eng.submit("a", x) for x in _samples(30, seed=2)]:
            f.result(30)
        s = eng.stats("a")
    assert s["n_requests"] == 30
    assert s["n_batches"] >= 1
    assert 0 < s["mean_batch_occupancy"] <= 1.0
    for k in ("p50_ms", "p95_ms", "p99_ms", "mean_ms", "throughput_rps"):
        assert np.isfinite(s[k]) and s[k] >= 0
    assert s["buckets"][-1] == 8


def test_stats_bucket_histograms(designs):
    """Per-bucket hit histogram and jit-compile counts: warmup compiles
    every bucket, dispatched batches land in exactly one bucket each,
    and the totals reconcile with n_batches."""
    with ServeEngine(max_batch=8, max_wait_us=100.0) as eng:
        eng.register("a", designs["a"])
        s0 = eng.stats("a")
        # fresh runner: nothing hit, nothing compiled yet
        assert s0["bucket_hits"] == {1: 0, 2: 0, 4: 0, 8: 0}
        assert s0["jit_compiles"] == {1: 0, 2: 0, 4: 0, 8: 0}
        assert s0["n_jit_compiles"] == 0
        eng.warmup("a")
        s1 = eng.stats("a")
        # warmup compiles every bucket shape but dispatches no batches
        assert s1["jit_compiles"] == {1: 1, 2: 1, 4: 1, 8: 1}
        assert s1["n_jit_compiles"] == 4
        assert sum(s1["bucket_hits"].values()) == 0
        # a lone request is a 1-element batch -> bucket 1, exactly once
        eng.submit("a", _samples(1, seed=3)[0]).result(30)
        s2 = eng.stats("a")
        assert s2["bucket_hits"][1] == 1
        assert sum(s2["bucket_hits"].values()) == 1
        # a burst: every dispatched batch lands in exactly one bucket
        for f in eng.submit_batch("a", _samples(20, seed=4)):
            f.result(30)
        s3 = eng.stats("a")
        assert sum(s3["bucket_hits"].values()) == s3["n_batches"]
        assert set(s3["bucket_hits"]) == {1, 2, 4, 8}
        # compiles never exceed one per bucket shape (jit caches by shape)
        assert all(c <= 1 for c in s3["jit_compiles"].values())


# -- sharded dispatch path ------------------------------------------------


def test_sharded_results_bit_identical(designs):
    """shards=4: same bits as direct forward_int, through both submit
    and submit_batch, with traffic spread over every shard."""
    design = designs["a"]
    xs = _samples(200)
    want = np.asarray(design.forward_int(xs))
    cfg = ServeConfig(max_batch=16, max_wait_us=100.0, shards=4)
    with ServeEngine(config=cfg) as eng:
        eng.register("a", design, warmup=True)
        futs = [eng.submit("a", x) for x in xs[:100]]
        futs += eng.submit_batch("a", xs[100:])
        got = np.stack([f.result(30) for f in futs])
        s = eng.stats("a")
    np.testing.assert_array_equal(got, want)
    assert s["n_shards"] == 4 and len(s["shards"]) == 4
    assert all(ss["n_requests"] > 0 for ss in s["shards"])  # round-robin


def test_per_shard_stats_consistency(designs):
    """Per-shard counters reconcile: sum(bucket_hits) == n_batches on
    every shard AND on the aggregate, request counts sum across shards,
    and the per-stage accounting covers every executed batch."""
    cfg = ServeConfig(max_batch=8, max_wait_us=100.0, shards=3)
    with ServeEngine(config=cfg) as eng:
        eng.register("a", designs["a"], warmup=True)
        for f in [eng.submit("a", x) for x in _samples(60, seed=7)]:
            f.result(30)
        for f in eng.submit_batch("a", _samples(40, seed=8)):
            f.result(30)
        s = eng.stats("a")
    for ss in s["shards"]:
        assert sum(ss["bucket_hits"].values()) == ss["n_batches"]
    assert sum(s["bucket_hits"].values()) == s["n_batches"]
    assert s["n_batches"] == sum(ss["n_batches"] for ss in s["shards"])
    assert s["n_requests"] == 100 == sum(ss["n_requests"] for ss in s["shards"])
    ps = s["per_stage"]
    assert ps["dispatch"]["count"] == s["n_batches"]
    assert ps["pad"]["count"] == s["n_batches"]
    assert ps["queue_wait"]["count"] == 100  # one sample per served request
    for rec in ps.values():
        assert np.isfinite(rec["total_ms"]) and rec["total_ms"] >= 0.0
        assert np.isfinite(rec["mean_us"]) and rec["mean_us"] >= 0.0


def test_warmup_failure_leaves_truthful_flags():
    """A warmup that raises mid-loop must flag only the buckets whose
    trace actually completed (pre-fix: flags were set before the call,
    reporting never-traced buckets as compiled)."""

    class _Boom:
        in_shape = (8,)

        @staticmethod
        def forward_int(x):
            if x.shape[0] >= 4:
                raise ValueError("boom bucket")
            return x

    runner = _ModelRunner("boom", _Boom(), 8, 16, 100.0, None, shards=2)
    with pytest.raises(ValueError, match="boom bucket"):
        runner.warmup()
    assert runner.jit_compiles == {1: 1, 2: 1, 4: 0, 8: 0}


def test_rejected_counter_exact_under_concurrency(designs):
    """n_rejected was a racy read-modify-write from submitter threads;
    now it is lock-guarded per shard, so the engine's count must equal
    the rejections the clients actually observed — exactly."""
    cfg = ServeConfig(
        max_batch=4, queue_depth=4, max_wait_us=200_000.0,
        backpressure="reject", shards=2,
    )
    eng = ServeEngine(config=cfg)
    try:
        eng.register("a", designs["a"], warmup=True)
        xs = _samples(64, seed=9)
        n_threads = 4
        rejects = [0] * n_threads
        accepted = [[] for _ in range(n_threads)]

        def flood(i):
            for x in xs:
                try:
                    accepted[i].append(eng.submit("a", x))
                except QueueFullError:
                    rejects[i] += 1

        threads = [
            threading.Thread(target=flood, args=(i,)) for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        total_rejected = sum(rejects)
        assert total_rejected > 0
        assert eng.stats("a")["n_rejected"] == total_rejected
        for futs in accepted:
            for f in futs:
                assert f.result(30).shape == (6,)
    finally:
        eng.shutdown()


# -- serving-shutdown stress: no future may ever hang ---------------------


def _resolve_all(futures, timeout=5.0):
    """Every future must resolve (result or exception) within timeout;
    returns (n_ok, n_failed) and fails the test on a hang."""
    n_ok = n_failed = 0
    for f in futures:
        try:
            exc = f.exception(timeout=timeout)
        except FutureTimeoutError:
            pytest.fail("future left hanging past the resolution timeout")
        if exc is None:
            n_ok += 1
        else:
            assert isinstance(exc, RuntimeError)  # closed / queue-full
            n_failed += 1
    return n_ok, n_failed


@pytest.mark.parametrize("shards", [1, 4])
def test_shutdown_stress_no_hung_futures(designs, shards):
    """Hammer submit + submit_batch from several threads while shutdown
    proceeds: every Future ever handed out resolves within a bounded
    timeout (the regression net for the put-after-final-sweep race)."""
    cfg = ServeConfig(max_batch=8, max_wait_us=200.0, shards=shards)
    eng = ServeEngine(config=cfg)
    eng.register("a", designs["a"], warmup=True)
    xs = _samples(8, seed=10)
    futures: list = []
    flock = threading.Lock()
    stop = threading.Event()

    def hammer(i):
        n = 0
        while not stop.is_set():
            try:
                if n % 3 == 0:
                    fs = eng.submit_batch("a", xs)
                else:
                    fs = [eng.submit("a", xs[n % len(xs)])]
            except (EngineClosedError, KeyError):
                break
            with flock:
                futures.extend(fs)
            n += 1

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    eng.shutdown(timeout=5.0)
    stop.set()
    for t in threads:
        t.join(5.0)
        assert not t.is_alive()
    n_ok, _ = _resolve_all(futures)
    assert n_ok > 0  # the drain served real traffic before closing


def test_unregister_race_futures_resolve(designs):
    """submit_batch racing unregister across repeated register/drop
    cycles: the drain serves what it can, fails the rest loudly with
    the shut-down error, and nothing hangs."""
    eng = ServeEngine(config=ServeConfig(max_batch=8, max_wait_us=100.0, shards=2))
    try:
        for trial in range(3):
            eng.register("a", designs["a"])
            xs = _samples(16, seed=11 + trial)
            futures: list = []
            flock = threading.Lock()

            def hammer():
                while True:
                    try:
                        fs = eng.submit_batch("a", xs)
                    except (KeyError, EngineClosedError):
                        return
                    with flock:
                        futures.extend(fs)

            threads = [threading.Thread(target=hammer) for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.05)
            eng.unregister("a", timeout=5.0)
            for t in threads:
                t.join(5.0)
                assert not t.is_alive()
            _resolve_all(futures)
    finally:
        eng.shutdown()


def test_rollout_drain_race_futures_resolve(designs):
    """Deployment rollout under concurrent traffic: the alias retry
    hides the flip from clients (no KeyError escapes), v1's in-flight
    futures complete during the drain, and every future resolves."""
    with Deployment(ServeConfig(max_batch=8, max_wait_us=100.0, shards=2)) as dep:
        dep.register("m", designs["a"])
        xs = _samples(8, seed=12)
        futures: list = []
        flock = threading.Lock()
        stop = threading.Event()
        escaped: list = []

        def hammer(i):
            n = 0
            while not stop.is_set():
                try:
                    if i % 2:
                        fs = dep.submit_batch("m", xs)
                    else:
                        fs = [dep.submit("m", xs[n % len(xs)])]
                except Exception as e:  # noqa: BLE001 - recorded and asserted
                    escaped.append(e)
                    return
                with flock:
                    futures.extend(fs)
                n += 1

        threads = [threading.Thread(target=hammer, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for _ in range(3):
            time.sleep(0.05)
            dep.register("m", designs["a"])  # rollout: flip alias, drain old
        stop.set()
        for t in threads:
            t.join(10.0)
            assert not t.is_alive()
        assert not escaped
        n_ok, _ = _resolve_all(futures)
        assert n_ok > 0


def test_blocked_submitters_wake_on_shutdown(designs):
    """Submitters blocked on a saturated queue (block policy) are woken
    by shutdown and fail fast with the shut-down error instead of
    deadlocking inside submit."""
    cfg = ServeConfig(max_batch=4, queue_depth=2, max_wait_us=500_000.0, shards=1)
    eng = ServeEngine(config=cfg)
    eng.register("a", designs["a"], warmup=True)
    xs = _samples(4, seed=13)
    futures: list = []
    flock = threading.Lock()
    outcome: list = []

    def pusher():
        try:
            while True:
                f = eng.submit("a", xs[0])
                with flock:
                    futures.append(f)
        except EngineClosedError:
            outcome.append("closed")

    threads = [threading.Thread(target=pusher) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.2)  # queue and slab saturate; pushers block in submit
    eng.shutdown(timeout=5.0)
    for t in threads:
        t.join(5.0)
        assert not t.is_alive()
    assert outcome == ["closed"] * 3
    _resolve_all(futures)


def test_submit_after_stop_fails_fast(designs):
    """The shutdown race, deterministically: a submitter that grabbed
    the runner reference just before shutdown popped it must fail fast
    on the put path (or get failed futures) — never enqueue into a
    dispatcherless queue."""
    eng = ServeEngine(config=ServeConfig(max_batch=4, shards=2))
    eng.register("a", designs["a"])
    runner = eng._runner("a")
    x = _samples(1, seed=14)[0]
    eng.shutdown()
    with pytest.raises(EngineClosedError, match="shut down"):
        runner.submit_one(x, time.perf_counter(), block=True)
    futs = runner.submit_many([x] * 3, time.perf_counter(), block=True)
    for f in futs:
        with pytest.raises(EngineClosedError, match="shut down"):
            f.result(1)


def test_dispatcher_stages_cover_its_wall_time(designs):
    """idle + batch_form + pad + dispatch + copy_out + observe of each
    shard add up to the time between two stats() calls (within 2% or
    2 ms) while a client keeps the shards busy with bursts of submits."""
    per_batch = ("idle", "batch_form", "pad", "dispatch", "copy_out", "observe")
    xs = _samples(64)
    stop = threading.Event()
    cfg = ServeConfig(max_batch=8, max_wait_us=100.0, shards=2)
    with ServeEngine(config=cfg) as eng:
        eng.register("a", designs["a"], warmup=True)

        def bursts():
            while not stop.is_set():
                for f in eng.submit_batch("a", xs):
                    f.result(30)

        client = threading.Thread(target=bursts)
        client.start()
        try:
            deadline = time.perf_counter() + 30
            while eng.stats("a")["n_batches"] < 64 and time.perf_counter() < deadline:
                time.sleep(0.01)
            t0 = time.perf_counter()
            s0 = eng.stats("a")
            time.sleep(2.0)
            s1 = eng.stats("a")
            t1 = time.perf_counter()
        finally:
            stop.set()
            client.join(30)
    assert not client.is_alive()
    window = t1 - t0
    for a, b in zip(s0["shards"], s1["shards"]):
        assert b["n_batches"] - a["n_batches"] > 10
        covered = sum(
            b["per_stage"][k]["total_ms"] - a["per_stage"][k]["total_ms"] for k in per_batch
        ) * 1e-3
        assert abs(covered - window) <= max(0.02 * window, 2e-3), (covered, window)


def test_ended_shard_threads_report_not_alive(designs):
    """A dispatcher's stop event must not shadow ``Thread._stop``: joining
    or asking ``is_alive()`` of an ended shard used to raise TypeError."""
    eng = ServeEngine(config=ServeConfig(max_batch=8, shards=2))
    eng.register("a", designs["a"])
    shards = list(eng._runners["a"].shards)
    eng.infer("a", _samples(1)[0])
    eng.shutdown()
    for sh in shards:
        sh.join(5)
        assert not sh.is_alive()
