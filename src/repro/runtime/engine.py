"""Sharded, microbatched serving engine for compiled DA designs.

The deployment model of the paper (and hls4ml): a design is compiled
once, then serves inference at fixed microsecond-scale latency.  This
engine is the software analogue of the always-ready FPGA datapath — a
multi-model registry where each registered ``CompiledDesign`` (in-memory
or cold-started from a ``save_design`` artifact) gets:

  * N dispatch *shards* (``ServeConfig.shards``), each a bounded request
    queue + dispatcher thread + preallocated payload slab; ``submit``
    places requests round-robin across shards, ``submit_batch`` spreads
    contiguous chunks, and the per-model ``queue_depth`` backpressure
    budget is divided across shards;
  * a payload **slab** per shard: submitters write samples straight into
    a preallocated ring of slots and dispatchers gather whole batches
    out of it with one vectorized copy into a bucket-shaped scratch
    array — no per-request array allocations or per-request copies on
    the dispatch path;
  * microbatch formation per shard — at most ``max_batch`` requests,
    waiting at most ``max_wait_us`` after the first — with bucketed
    batch shapes (powers of two up to ``max_batch``) so the jitted
    integer forward pass (shared by all shards) compiles once per
    bucket and every batch is padded to the next bucket;
  * per-request latency accounting (submit -> result, p50/p95/p99,
    throughput) plus per-stage accounting (queue wait per request; per
    batch idle / batch-form / pad / dispatch / copy-out / observe, which
    tile the dispatcher thread's wall time) and per-shard counters,
    merged across shards in ``stats()``;
  * one ``repro.obs.trace`` span per dispatcher stage and batch
    (``serve.idle``, ``serve.batch_form``, then ``serve.batch`` holding
    ``serve.pad`` / ``serve.dispatch`` / ``serve.copy_out`` /
    ``serve.observe``), which also land in an active JAX profiler
    session beside the runtime's own events.

Requests are single samples on the integer input grid (``in_shape``,
as ``CompiledDesign.forward_int`` consumes them); ``submit`` returns a
``concurrent.futures.Future`` resolving to the integer output.

Shutdown discipline: every Future handed out is resolved — with a
result while draining, or with :class:`EngineClosedError` once the
model is closed.  The closed flag is checked *under the shard lock* on
every enqueue, so a ``submit`` that grabbed a runner reference just
before ``unregister``/``shutdown`` popped it either lands in the queue
before the dispatcher's final drain (and is served) or observes the
flag and fails fast — the put-after-final-sweep window that used to
hang futures cannot occur.

Resilience layer (docs/robustness.md; provoked end-to-end by
``tests/test_chaos.py`` through :mod:`repro.chaos`):

  * **Deadlines + shedding** — requests may carry a deadline (per call
    or ``ServeConfig.deadline_ms``); expired requests are failed with
    :class:`DeadlineExceededError` at enqueue and again at batch-form
    time (``n_shed``) instead of burning dispatcher work.
  * **Circuit breaker** — consecutive jit-dispatch failures trip a
    per-model :class:`~repro.runtime.resilience.CircuitBreaker`
    (closed -> open -> half-open probes with capped exponential
    backoff); while open, batches fail fast with
    :class:`CircuitOpenError` or degrade to the bit-exact numpy
    interpreter (``ServeConfig.fallback="interpreter"``).
  * **Shard supervision** — a per-model supervisor thread detects dead
    dispatcher threads, fails their in-flight/pending futures with
    :class:`ShardCrashedError`, restarts them within
    ``ServeConfig.restart_budget``, then escalates to
    :class:`ModelUnhealthyError`.
  * **Client-timeout accounting** — ``infer`` ties its ``timeout`` into
    the deadline path (abandoned work is shed, not executed) and counts
    expiries in ``n_client_timeouts``.

The core invariant, asserted by the chaos soak under every injected
fault schedule: *every submitted Future resolves — with a result or a
typed error — and every slab slot returns to the free list.*
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError  # noqa: F401
from pathlib import Path

import jax
import numpy as np

from ..chaos import ThreadKillFault, fault_point
from ..flow.config import UNSET, ServeConfig, resolve_legacy
from ..nn.compiler import CompiledDesign
from ..obs import trace
from ..obs.flight import FlightRecorder
from ..obs.metrics import Histogram, get_registry, render_prometheus
from .artifact import load_design
from .metrics import LatencyRecorder, StageAccumulator
from .resilience import CircuitBreaker

# spans land in the JAX profiler's trace while a session is active
trace.set_profiler_sink(jax.profiler.TraceAnnotation)


def _serve_config_from_legacy(legacy: dict) -> ServeConfig:
    if "overflow" in legacy:
        legacy["backpressure"] = legacy.pop("overflow")
    if legacy.get("buckets") is not None:
        legacy["buckets"] = tuple(legacy["buckets"])
    return ServeConfig(**legacy)


class QueueFullError(RuntimeError):
    """Raised by ``submit`` when overflow policy is "reject" and the
    model's request queue is at capacity."""


class EngineClosedError(RuntimeError):
    """Raised by ``submit`` (or set on a Future) when the request raced
    ``unregister``/``shutdown``: the model's dispatchers are stopping or
    gone, so the request is failed fast instead of queued forever."""


class DeadlineExceededError(RuntimeError):
    """The request's deadline expired before dispatch; it was shed
    (counted in ``n_shed``) instead of executed."""


class CircuitOpenError(RuntimeError):
    """The model's circuit breaker is open and no fallback is
    configured: the request failed fast instead of hitting the broken
    dispatch path (counted in ``n_fast_failed``)."""


class ShardCrashedError(RuntimeError):
    """The dispatch shard's thread died; its in-flight and pending
    futures were failed with this error.  With supervision enabled the
    shard is restarted and new submits retry onto the replacement."""


class ModelUnhealthyError(RuntimeError):
    """The model exhausted its dispatcher restart budget (or crashed
    with supervision disabled); submits fail fast until it is
    re-registered."""


class _Request:
    __slots__ = ("slot", "t_submit", "future", "tid", "deadline")

    def __init__(
        self,
        slot: int,
        t_submit: float,
        future: Future,
        tid: int = 0,
        deadline: float | None = None,
    ):
        self.slot = slot
        self.t_submit = t_submit
        self.future = future
        self.tid = tid  # per-shard trace id, stamped at enqueue
        self.deadline = deadline  # absolute perf_counter seconds, or None


def _default_buckets(max_batch: int) -> tuple[int, ...]:
    out = [1]
    while out[-1] < max_batch:
        out.append(min(out[-1] * 2, max_batch))
    return tuple(out)


class _Shard(threading.Thread):
    """One dispatch lane of a model: bounded request deque + payload
    slab + dispatcher thread.

    All shard state (deque, free-slot stack, counters) is guarded by one
    lock; submitters copy their sample into a reserved slab slot while
    holding it (the samples are small — the copy is cheaper than a
    second lock round-trip), and the dispatcher drains a whole batch in
    a single lock acquisition, then gathers the batch out of the slab
    with one vectorized copy into a per-bucket scratch array.

    Crash discipline: the dispatcher loop is wrapped in a
    ``BaseException`` handler (injected thread kills are
    ``BaseException`` precisely so they get past the per-batch
    ``except Exception`` guard).  On crash the shard marks itself dead,
    fails its in-flight and pending futures with
    :class:`ShardCrashedError`, wakes blocked submitters, and sets
    ``_drained`` — a dead shard never strands a future or a slab slot.
    """

    def __init__(self, runner: "_ModelRunner", idx: int, depth: int):
        super().__init__(
            daemon=True, name=f"da4ml-serve-{runner.model_name}-s{idx}"
        )
        self.runner = runner
        self.idx = idx
        self.depth = depth
        self.max_batch = runner.max_batch
        self.max_wait_s = runner.max_wait_s
        self.in_shape = runner.in_shape
        self._fn = runner._fn
        self._fallback_fn = runner._fallback_fn
        self._closed = runner._closed  # runner-wide: set first in stop()

        # payload slab: depth queued + max_batch executing slots can be
        # live at once; slots are recycled through a free-list stack
        cap = depth + runner.max_batch
        self.slab = np.empty((cap, *self.in_shape), np.int32)
        self._free: list[int] = list(range(cap))
        self._pending: deque[_Request] = deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        # bucket-shaped scratch: the gather target, reused every batch
        # (safe: the jitted call's result is materialized before reuse)
        self._scratch = {
            b: np.zeros((b, *self.in_shape), np.int32) for b in runner.buckets
        }

        self.metrics = LatencyRecorder()
        self.stage = StageAccumulator()
        # observability (single writer: this dispatcher thread) — per-stage
        # µs histograms and the per-request flight recorder; trace ids are
        # stamped at enqueue under the shard lock (shard idx in high bits
        # keeps them unique across shards)
        self.stage_hist = {s: Histogram() for s in StageAccumulator.STAGES}
        self.flight = FlightRecorder(capacity=2048, slow_k=16)
        self._tid_seq = itertools.count()
        self._tid_base = idx << 40
        self._batch_seq = itertools.count()  # numbers this shard's serve.batch spans
        self._t_mark = 0.0  # where the dispatcher's current stage began
        self.n_batches = 0
        self.n_rejected = 0  # guarded by self._lock (shared with submitters)
        self.n_shed = 0  # guarded by self._lock (submitters + dispatcher)
        self.n_fast_failed = 0  # dispatcher-only writer
        self.n_fallback_batches = 0  # dispatcher-only writer
        self._occupancy_sum = 0.0
        self.bucket_hits: dict[int, int] = {b: 0 for b in runner.buckets}
        # not `_stop`: that would shadow threading.Thread._stop()
        self._stop_event = threading.Event()
        self._drained = threading.Event()
        # crash state: flipped once by _on_crash, read under the lock by
        # submitters and lock-free by the supervisor
        self.dead = False
        self.crash_exc: BaseException | None = None
        self.heartbeat = time.perf_counter()
        self._executing: list[_Request] = []  # claimed, awaiting dispatch

    # -- enqueue (submitter threads) -----------------------------------
    def _closed_error(self) -> EngineClosedError:
        return EngineClosedError(
            f"model {self.runner.model_name!r}: engine shut down"
        )

    def _full_error(self) -> QueueFullError:
        return QueueFullError(
            f"queue for model {self.runner.model_name!r} is full "
            f"({self.depth} requests on shard {self.idx})"
        )

    def _crash_error(self) -> ShardCrashedError:
        return ShardCrashedError(
            f"model {self.runner.model_name!r}: dispatch shard {self.idx} "
            f"crashed ({self.crash_exc!r})"
        )

    def _deadline_error(self) -> DeadlineExceededError:
        return DeadlineExceededError(
            f"model {self.runner.model_name!r}: deadline expired before "
            "dispatch (request shed)"
        )

    def _final_error(self) -> RuntimeError:
        return self._crash_error() if self.dead else self._closed_error()

    def put_one(
        self, x: np.ndarray, t_submit: float, block: bool,
        deadline: float | None = None,
    ) -> Future:
        fut: Future = Future()
        if deadline is not None and t_submit >= deadline:
            # the caller handed us an already-expired budget: shed at
            # the door, before a slab slot is even reserved
            with self._lock:
                self.n_shed += 1
            if fut.set_running_or_notify_cancel():
                fut.set_exception(self._deadline_error())
            return fut
        with self._lock:
            while True:
                if self.dead:
                    raise self._crash_error()
                if self._closed.is_set():
                    raise self._closed_error()
                if self._free and len(self._pending) < self.depth:
                    break
                if not block:
                    self.n_rejected += 1
                    raise self._full_error()
                # timed wait: re-checks the closed flag even if a racing
                # stop() notified before we started waiting
                self._not_full.wait(0.05)
            slot = self._free.pop()
            self.slab[slot] = x
            self._pending.append(
                _Request(
                    slot, t_submit, fut,
                    self._tid_base | next(self._tid_seq), deadline,
                )
            )
            self._not_empty.notify()
        return fut

    def put_many(
        self, xs: list, t_submit: float, block: bool,
        deadline: float | None = None,
    ) -> list[Future]:
        """Enqueue a chunk under one lock acquisition.  With the reject
        policy, overflowing samples' futures are *failed* with
        :class:`QueueFullError` (and counted) instead of raising; if the
        shard closes (or crashes) mid-chunk the remaining futures are
        failed with :class:`EngineClosedError` /
        :class:`ShardCrashedError` — every returned Future resolves."""
        futs: list[Future] = [Future() for _ in xs]
        if deadline is not None and t_submit >= deadline:
            with self._lock:
                self.n_shed += len(xs)
            err = self._deadline_error()
            for f in futs:
                if f.set_running_or_notify_cancel():
                    f.set_exception(err)
            return futs
        i, n = 0, len(xs)
        with self._lock:
            while i < n:
                if self.dead or self._closed.is_set():
                    break
                space = min(len(self._free), self.depth - len(self._pending))
                if space <= 0:
                    if not block:
                        self.n_rejected += 1
                        f = futs[i]
                        if f.set_running_or_notify_cancel():
                            f.set_exception(self._full_error())
                        i += 1
                        continue
                    self._not_full.wait(0.05)
                    continue
                for j in range(i, min(i + space, n)):
                    slot = self._free.pop()
                    self.slab[slot] = xs[j]
                    self._pending.append(
                        _Request(
                            slot, t_submit, futs[j],
                            self._tid_base | next(self._tid_seq), deadline,
                        )
                    )
                i = min(i + space, n)
                self._not_empty.notify()
        for j in range(i, n):  # chunk tail cut off by a racing shutdown/crash
            f = futs[j]
            if f.set_running_or_notify_cancel():
                f.set_exception(self._final_error())
        return futs

    # -- dispatcher ----------------------------------------------------
    def run(self) -> None:
        try:
            self._t_mark = time.perf_counter()
            while True:
                self.heartbeat = time.perf_counter()
                fault_point("serve.dispatcher")
                batch, t_first = self._collect()
                if batch:
                    with trace.span(
                        "serve.batch", shard=self.idx, seq=next(self._batch_seq),
                        bucket=self._bucket(len(batch)), n=len(batch),
                        first_tid=batch[0].tid, last_tid=batch[-1].tid,
                    ):
                        self._execute(batch, t_first)
                elif self._stop_event.is_set():
                    break
            self._fail_pending(self._closed_error)
            self._drained.set()
        except BaseException as e:  # dispatcher death: clean up, never strand
            self._on_crash(e)

    def _lap(self, stage: str) -> float:
        """Charge the time since the last lap to ``stage`` (one batch)
        and return the clock reading that ends it.  The laps of one
        loop run back to back, so the batch stages tile the dispatcher
        thread's wall time; what a failed or wholly shed batch leaves
        uncharged falls into the next ``idle``."""
        t = time.perf_counter()
        dt = t - self._t_mark
        self._t_mark = t
        self.stage.add(stage, dt)
        self.stage_hist[stage].observe(dt * 1e6)
        return t

    def _collect(self) -> tuple[list[_Request], float]:
        with self._lock:
            with trace.span("serve.idle", shard=self.idx):
                while not self._pending:
                    if self._stop_event.is_set():
                        return [], 0.0
                    self.heartbeat = time.perf_counter()
                    self._not_empty.wait(0.05)
            t_first = self._lap("idle")
            with trace.span("serve.batch_form", shard=self.idx):
                if len(self._pending) < self.max_batch and not self._stop_event.is_set():
                    deadline = t_first + self.max_wait_s
                    while len(self._pending) < self.max_batch:
                        rem = deadline - time.perf_counter()
                        if rem <= 0 or self._stop_event.is_set():
                            break
                        self._not_empty.wait(min(rem, 0.02))
                n = min(len(self._pending), self.max_batch)
                batch = [self._pending.popleft() for _ in range(n)]
            self._not_full.notify_all()
            return batch, t_first

    def _free_slots(self, slots: list) -> None:
        with self._lock:
            self._free.extend(slots)
            self._not_full.notify_all()

    def _fail_pending(self, err_factory) -> None:
        """Fail any requests still queued once the dispatcher is gone
        (drain timeout or crash) instead of leaving their futures to
        hang until the client's result() timeout."""
        with self._lock:
            reqs = list(self._pending)
            self._pending.clear()
            self._free.extend(r.slot for r in reqs)
            self._not_full.notify_all()
        for r in reqs:
            if r.future.set_running_or_notify_cancel():
                r.future.set_exception(err_factory())

    def _on_crash(self, exc: BaseException) -> None:
        """Dispatcher-thread death: mark dead, wake blocked submitters,
        fail in-flight and pending futures, release their slots, and
        report to the runner (which escalates or lets the supervisor
        revive this lane)."""
        self.crash_exc = exc
        with self._lock:
            self.dead = True
            self._not_empty.notify_all()
            self._not_full.notify_all()
        claimed, self._executing = self._executing, []
        for r in claimed:
            if not r.future.done():
                r.future.set_exception(self._crash_error())
        self._fail_pending(self._crash_error)
        self._drained.set()
        self.runner._note_crash(self, exc)

    def _bucket(self, n: int) -> int:
        for b in self.runner.buckets:
            if b >= n:
                return b
        return self.runner.buckets[-1]

    def _dispatch(self, x: np.ndarray) -> tuple[np.ndarray, bool]:
        """Run one padded batch through the breaker-routed dispatch path.
        Returns (outputs, used_fallback)."""
        breaker = self.runner.breaker
        route = breaker.route()
        if route == "reject":
            if self._fallback_fn is not None:
                return np.asarray(self._fallback_fn(x)), True
            raise CircuitOpenError(
                f"model {self.runner.model_name!r}: circuit breaker open "
                "and no fallback configured"
            )
        probe = route == "probe"
        try:
            fault_point("serve.dispatch")
            y = np.asarray(self._fn(x))
        except ThreadKillFault:
            breaker.record(ok=False, probe=probe)  # never leave a probe hung
            raise
        except Exception:
            breaker.record(ok=False, probe=probe)
            if self._fallback_fn is not None:
                return np.asarray(self._fallback_fn(x)), True
            raise
        breaker.record(ok=True, probe=probe)
        return y, False

    def _fail_batch(self, claimed: list[_Request], e: Exception) -> None:
        """Resolve a failed batch's futures instead of killing the thread."""
        self._executing = []
        if isinstance(e, CircuitOpenError):
            self.n_fast_failed += len(claimed)
        for r in claimed:
            r.future.set_exception(e)

    def _execute(self, batch: list[_Request], t_first: float) -> None:
        t_formed = self._lap("batch_form")
        with trace.span("serve.pad"):
            # claim the futures; drop any the client cancelled while queued,
            # shed any whose deadline expired while they sat in the queue
            claimed: list[_Request] = []
            expired: list[_Request] = []
            for r in batch:
                if not r.future.set_running_or_notify_cancel():
                    continue
                if r.deadline is not None and t_formed >= r.deadline:
                    expired.append(r)
                else:
                    claimed.append(r)
            slots = [r.slot for r in batch]
            if expired:
                with self._lock:
                    self.n_shed += len(expired)
                for r in expired:
                    r.future.set_exception(self._deadline_error())
            if not claimed:
                self._free_slots(slots)
                return
            self.stage.add(
                "queue_wait",
                sum(t_formed - r.t_submit for r in claimed),
                len(claimed),
            )
            n = len(claimed)
            b = self._bucket(n)
            x = self._scratch[b]
            # the crash handler fails these if we die before they resolve;
            # a ThreadKillFault passes the guards below to run()'s handler
            self._executing = claimed
            try:
                try:
                    fault_point("serve.gather")
                    x[:n] = self.slab[[r.slot for r in claimed]]
                    if n < b:
                        x[n:] = 0
                finally:
                    self._free_slots(slots)  # slots recycle even on failure
            except Exception as e:
                self._fail_batch(claimed, e)
                return
        t_pad = self._lap("pad")
        with trace.span("serve.dispatch"):
            try:
                y, used_fallback = self._dispatch(x)
            except Exception as e:
                self._fail_batch(claimed, e)
                return
        self._executing = []
        t_done = self._lap("dispatch")
        with trace.span("serve.copy_out"):
            if used_fallback:
                self.n_fallback_batches += 1
            lats = []
            for i, r in enumerate(claimed):
                r.future.set_result(y[i])
                lats.append(t_done - r.t_submit)
            self.metrics.record_many(lats, t_done)
            self.n_batches += 1
            # counted only on success, keeping sum(bucket_hits) == n_batches
            self.bucket_hits[b] += 1
            jc = self.runner.jit_compiles
            if not used_fallback and not jc[b]:
                jc[b] = 1  # first dispatch of this shape compiled (any shard)
            self._occupancy_sum += n / b
        t_out = self._lap("copy_out")
        with trace.span("serve.observe"):
            self._observe_batch(claimed, lats, b, n, t_first, t_formed, t_pad, t_done, t_out)
        self._lap("observe")

    def _observe_batch(
        self, claimed, lats, b, n, t_first, t_formed, t_pad, t_done, t_out
    ) -> None:
        """Feed the queue-wait histogram, the flight recorder, and the
        process-registry gauges after a successful batch.  This thread is
        the sole writer of all three, so the path stays lock-free; the
        batch-shared stage times are charged to every request's flight
        record while queue_wait stays per-request."""
        bf_us = (t_formed - t_first) * 1e6
        pad_us = (t_pad - t_formed) * 1e6
        disp_us = (t_done - t_pad) * 1e6
        out_us = (t_out - t_done) * 1e6
        qh = self.stage_hist["queue_wait"]
        fl = self.flight
        ts_us = t_done * 1e6
        for r, lat in zip(claimed, lats):
            qw_us = (t_formed - r.t_submit) * 1e6
            qh.observe(qw_us)
            fl.record(
                r.tid, self.idx, b, n, lat * 1e6,
                (qw_us, bf_us, pad_us, disp_us, out_us), ts_us=ts_us,
            )
        # unlocked reads: both lens are single CPython ops, and a gauge
        # only needs to be approximately current
        reg = get_registry()
        model = self.runner.model_name
        reg.set_gauge(
            "serve_queue_depth", len(self._pending), model=model, shard=self.idx
        )
        reg.set_gauge(
            "serve_slab_occupancy",
            1.0 - len(self._free) / self.slab.shape[0],
            model=model, shard=self.idx,
        )

    # -- control -------------------------------------------------------
    def initiate_stop(self) -> None:
        self._stop_event.set()
        with self._lock:
            self._not_empty.notify_all()
            self._not_full.notify_all()

    def snapshot(self) -> dict:
        with self._lock:
            qsize = len(self._pending)
            n_rejected = self.n_rejected
            n_shed = self.n_shed
        n_batches = self.n_batches
        return {
            "shard": self.idx,
            "n_batches": n_batches,
            "n_rejected": n_rejected,
            "n_shed": n_shed,
            "n_fast_failed": self.n_fast_failed,
            "n_fallback_batches": self.n_fallback_batches,
            "n_requests": self.metrics.n_total,
            "queue_depth": qsize,
            "dead": self.dead,
            "heartbeat_age_s": max(0.0, time.perf_counter() - self.heartbeat),
            "mean_batch_occupancy": (
                self._occupancy_sum / n_batches if n_batches else 0.0
            ),
            "bucket_hits": {int(b): int(c) for b, c in self.bucket_hits.items()},
            "per_stage": self.stage.snapshot(),
            "flight": self.flight.snapshot(),
        }


class _Supervisor(threading.Thread):
    """Per-model watchdog: polls the runner's dispatcher threads and
    revives dead ones (heartbeat staleness is surfaced in ``stats()``;
    thread death — crash flag or ``Thread.is_alive`` — triggers the
    restart path)."""

    def __init__(self, runner: "_ModelRunner", interval_s: float = 0.05):
        super().__init__(daemon=True, name=f"da4ml-supervise-{runner.model_name}")
        self.runner = runner
        self.interval_s = interval_s

    def run(self) -> None:
        r = self.runner
        while not r._closed.wait(self.interval_s):
            for idx in range(r.n_shards):
                sh = r.shards[idx]
                if sh.ident is None:
                    continue  # not started yet
                if (sh.dead or not sh.is_alive()) and not sh._stop_event.is_set():
                    r._revive(idx, sh)


class _ModelRunner:
    """One registered model: shared jitted forward + N dispatch shards
    + circuit breaker + (optional) supervisor."""

    def __init__(
        self,
        name: str,
        design: CompiledDesign,
        max_batch: int,
        queue_depth: int,
        max_wait_us: float,
        buckets: tuple[int, ...] | None,
        shards: int = 1,
        config: ServeConfig | None = None,
    ):
        self.model_name = name
        self.design = design
        self.max_batch = max_batch
        self.max_wait_s = max_wait_us * 1e-6
        self.buckets = tuple(sorted(buckets)) if buckets else _default_buckets(max_batch)
        if self.buckets[-1] < max_batch:
            raise ValueError("largest bucket must cover max_batch")
        self.in_shape = tuple(design.in_shape)
        self._fn = jax.jit(design.forward_int)
        # resilience knobs come from the ServeConfig; the engine params
        # above stay positional for backward compatibility
        rcfg = config if config is not None else ServeConfig()
        self.supervise = rcfg.supervise
        self.restart_budget = rcfg.restart_budget
        self.deadline_default_s = (
            rcfg.deadline_ms * 1e-3 if rcfg.deadline_ms is not None else None
        )
        self._fallback_fn = None
        if rcfg.fallback == "interpreter":
            from ..nn.interpreter import numpy_forward_fn  # lazy: nn imports stay light

            self._fallback_fn = numpy_forward_fn(design)
        self.breaker = CircuitBreaker(
            threshold=rcfg.breaker_threshold,
            cooldown_s=rcfg.breaker_cooldown_ms * 1e-3,
            cooldown_max_s=rcfg.breaker_cooldown_max_ms * 1e-3,
            on_event=self._breaker_event,
        )
        # lifecycle events (breaker transitions, crashes, restarts) land
        # in a runner-level recorder merged into the stats flight view
        self.flight_events = FlightRecorder(capacity=8, slow_k=0)
        # which bucket shapes have been jit-compiled (0/1 per bucket;
        # jax caches per shape for a fixed design, and the jitted fn is
        # shared by every shard).  A flag is set only *after* a trace
        # actually completed — warmup or first dispatch — so a warmup
        # that raises mid-loop never reports untraced buckets as
        # compiled.  Without an up-front warmup, flags flipping
        # mid-traffic are exactly the requests that paid a compile.
        self.jit_compiles: dict[int, int] = {b: 0 for b in self.buckets}
        self.n_shards = max(1, int(shards))
        # the per-model queue_depth backpressure budget is divided
        # across shards (ceil, so capacity never shrinks below it)
        depth = -(-queue_depth // self.n_shards)
        self._depth = depth
        self._closed = threading.Event()
        self.shards = [_Shard(self, i, depth) for i in range(self.n_shards)]
        self._rr = itertools.count()  # round-robin placement cursor
        # supervision state: restart accounting + health flag, guarded by
        # _restart_lock (shards list swaps happen under it too)
        self._restart_lock = threading.Lock()
        self._count_lock = threading.Lock()
        self._retired: list[_Shard] = []
        self.restarts_used = [0] * self.n_shards
        self.n_restarts = 0
        self.n_crashes = 0
        self.n_client_timeouts = 0
        self.healthy = True
        self._supervisor: _Supervisor | None = None

    def start(self) -> None:
        for sh in self.shards:
            sh.start()
        if self.supervise and self._supervisor is None:
            self._supervisor = _Supervisor(self)
            self._supervisor.start()

    # -- resilience plumbing -------------------------------------------
    def _record_event(self, kind: str, **fields) -> None:
        self.flight_events.record_event(
            kind, ts_us=time.perf_counter() * 1e6, **fields
        )

    def _breaker_event(self, kind: str, snap: dict) -> None:
        self._record_event(
            kind,
            state=snap["state"],
            n_trips=snap["n_trips"],
            n_reopens=snap["n_reopens"],
            n_recoveries=snap["n_recoveries"],
            cooldown_s=snap["cooldown_s"],
        )

    def _note_crash(self, shard: _Shard, exc: BaseException) -> None:
        with self._count_lock:
            self.n_crashes += 1
        self._record_event("shard_crash", shard=shard.idx, error=repr(exc))
        if not self.supervise and self.healthy:
            # nobody will revive this lane: fail the model loudly rather
            # than letting submits bounce off a permanently dead shard
            self.healthy = False
            self._record_event(
                "model_unhealthy", shard=shard.idx,
                reason="crash with supervision disabled",
            )

    def _revive(self, idx: int, dead_shard: _Shard) -> None:
        """Swap a fresh dispatcher in for a dead one (supervisor thread).
        Budget-limited: exhausting ``restart_budget`` on a lane marks
        the whole model unhealthy instead of restart-looping forever."""
        with self._restart_lock:
            if self._closed.is_set() or self.shards[idx] is not dead_shard:
                return
            if not dead_shard.dead:
                # the thread died without running its crash handler
                # (the handler catches BaseException, so this is a
                # belt-and-braces path) — never leave futures hanging
                dead_shard._on_crash(RuntimeError("dispatcher thread died"))
            if self.restarts_used[idx] >= self.restart_budget:
                if self.healthy:
                    self.healthy = False
                    self._record_event(
                        "model_unhealthy", shard=idx,
                        reason="restart budget exhausted",
                        restarts=self.restarts_used[idx],
                    )
                return
            fresh = _Shard(self, idx, self._depth)
            self.restarts_used[idx] += 1
            self.n_restarts += 1
            self._retired.append(dead_shard)
            self.shards[idx] = fresh
            fresh.start()
            self._record_event(
                "shard_restart", shard=idx, restart_n=self.restarts_used[idx]
            )

    def count_client_timeout(self) -> None:
        with self._count_lock:
            self.n_client_timeouts += 1

    def _unhealthy_error(self) -> ModelUnhealthyError:
        return ModelUnhealthyError(
            f"model {self.model_name!r} is unhealthy "
            f"(dispatcher restart budget of {self.restart_budget} exhausted)"
        )

    def deadline_abs(self, t_submit: float, deadline_s: float | None) -> float | None:
        """Absolute deadline for a request: per-call value wins, then
        the config default, then None (no deadline)."""
        if deadline_s is None:
            if self.deadline_default_s is None:
                return None
            deadline_s = self.deadline_default_s
        return t_submit + deadline_s

    # -- serving -------------------------------------------------------
    def submit_one(
        self, x: np.ndarray, t_submit: float, block: bool,
        deadline: float | None = None,
    ) -> Future:
        last: ShardCrashedError | None = None
        for _ in range(8):
            if not self.healthy:
                raise self._unhealthy_error()
            sh = self.shards[next(self._rr) % self.n_shards]
            try:
                return sh.put_one(x, t_submit, block, deadline)
            except ShardCrashedError as e:
                last = e
                if self._closed.is_set() or not self.supervise:
                    raise
                # the retry window must outlast one supervisor poll
                # interval, or a submit racing the revive fails spuriously
                time.sleep(0.02)
        if not self.healthy:
            raise self._unhealthy_error()
        assert last is not None
        raise last

    def submit_many(
        self, xs: list, t_submit: float, block: bool,
        deadline: float | None = None,
    ) -> list[Future]:
        if not self.healthy:
            raise self._unhealthy_error()
        if self.n_shards == 1 or len(xs) <= 1:
            sh = self.shards[next(self._rr) % self.n_shards]
            return sh.put_many(xs, t_submit, block, deadline)
        # contiguous chunks, one per shard round-robin: one lock
        # acquisition per shard instead of one per request
        chunk = -(-len(xs) // self.n_shards)
        futs: list[Future] = []
        for i in range(0, len(xs), chunk):
            sh = self.shards[next(self._rr) % self.n_shards]
            futs.extend(sh.put_many(xs[i : i + chunk], t_submit, block, deadline))
        return futs

    # -- control -------------------------------------------------------
    def warmup(self) -> float:
        """Compile every bucket shape up front; returns wall seconds.
        Flags are set per bucket only after its trace+run returned, so a
        mid-loop failure leaves only truthful flags behind."""
        t0 = time.perf_counter()
        for b in self.buckets:
            np.asarray(self._fn(np.zeros((b, *self.in_shape), np.int32)))
            self.jit_compiles[b] = 1
        return time.perf_counter() - t0

    def stop(self, timeout: float = 5.0) -> None:
        # closed first: from here on every enqueue attempt fails fast
        # (checked under the shard lock, closing the put-after-sweep
        # race) and the supervisor revives nothing; already-queued
        # requests are still drained and served.
        self._closed.set()
        with self._restart_lock:  # no shard swap can race the drain below
            shards = list(self.shards)
        for sh in shards:
            sh.initiate_stop()
        deadline = time.perf_counter() + timeout
        for sh in shards:
            if sh.dead:
                continue  # crashed: its handler already set _drained —
                # don't burn the live shards' drain budget waiting on it
            sh._drained.wait(max(0.0, deadline - time.perf_counter()))
        for sh in shards:
            # drain timed out, or the shard died before stop() was even
            # called: fail leftovers loudly (typed by how the lane ended)
            sh._fail_pending(sh._final_error)
        if self._supervisor is not None:
            self._supervisor.join(timeout=1.0)

    def stats(self) -> dict:
        with self._restart_lock:
            live = list(self.shards)
            retired = list(self._retired)
            restarts_used = list(self.restarts_used)
        all_shards = retired + live
        shard_snaps = []
        for sh in all_shards:
            snap = sh.snapshot()
            snap["retired"] = sh in retired
            shard_snaps.append(snap)
        s = LatencyRecorder.merged_snapshot([sh.metrics for sh in all_shards])
        bucket_hits = {int(b): 0 for b in self.buckets}
        n_batches = n_rejected = n_shed = n_fast_failed = n_fallback = qdepth = 0
        occupancy = 0.0
        for sh, snap in zip(all_shards, shard_snaps):
            n_batches += snap["n_batches"]
            n_rejected += snap["n_rejected"]
            n_shed += snap["n_shed"]
            n_fast_failed += snap["n_fast_failed"]
            n_fallback += snap["n_fallback_batches"]
            qdepth += snap["queue_depth"]
            occupancy += sh._occupancy_sum
            for b, c in snap["bucket_hits"].items():
                bucket_hits[b] += c
        with self._count_lock:
            n_client_timeouts = self.n_client_timeouts
            n_crashes = self.n_crashes
        s.update(
            model=self.model_name,
            n_shards=self.n_shards,
            n_batches=n_batches,
            n_rejected=n_rejected,
            n_shed=n_shed,
            n_fast_failed=n_fast_failed,
            n_fallback_batches=n_fallback,
            n_client_timeouts=n_client_timeouts,
            queue_depth=qdepth,
            mean_batch_occupancy=(occupancy / n_batches if n_batches else 0.0),
            buckets=list(self.buckets),
            # aggregated bucket hit histogram + which bucket shapes have
            # been jit compiled; per-shard histograms (each satisfying
            # sum(bucket_hits) == n_batches) live under "shards"
            bucket_hits=bucket_hits,
            jit_compiles={int(b): int(c) for b, c in self.jit_compiles.items()},
            n_jit_compiles=int(sum(self.jit_compiles.values())),
            per_stage=StageAccumulator.merged_snapshot(
                [sh.stage for sh in all_shards]
            ),
            # cross-shard flight view: overall slowest-K request records
            # plus time-ordered lifecycle events (breaker transitions,
            # crashes, restarts) from the runner-level recorder
            flight=FlightRecorder.merged(
                [sh.flight for sh in all_shards] + [self.flight_events]
            ),
            breaker=self.breaker.snapshot(),
            supervision={
                "supervise": self.supervise,
                "healthy": self.healthy,
                "n_crashes": n_crashes,
                "n_restarts": self.n_restarts,
                "restart_budget": self.restart_budget,
                "restarts_used": restarts_used,
            },
            shards=shard_snaps,
        )
        return s


class ServeEngine:
    """Multi-model registry + sharded microbatched dispatch over
    compiled designs.

    The canonical way to set knobs is ``config=``, a
    :class:`repro.flow.ServeConfig` (max_batch, max_wait_us,
    queue_depth, backpressure, buckets, shards, plus the resilience
    knobs: deadline_ms, fallback, breaker_*, supervise,
    restart_budget); this is what ``Flow.serve`` constructs.  The
    individual kwargs are a deprecated shim kept for one release
    (``overflow`` maps to ``backpressure``): they construct the
    equivalent config and delegate.

    ``register`` rejects duplicate model names loudly — replacing a
    model in place would silently mix two designs' results under one
    name.  Rolling a model forward is a *versioning* operation:
    ``repro.flow.Deployment.register(name, design, version=...)`` gives
    register-v2 / atomic-alias-flip / drain-v1 semantics on top of this
    engine.
    """

    def __init__(
        self,
        max_batch=UNSET,
        queue_depth=UNSET,
        max_wait_us=UNSET,
        buckets=UNSET,
        overflow=UNSET,
        config: ServeConfig | None = None,
    ):
        legacy = {
            name: val
            for name, val in (
                ("max_batch", max_batch),
                ("queue_depth", queue_depth),
                ("max_wait_us", max_wait_us),
                ("buckets", buckets),
                ("overflow", overflow),
            )
            if val is not UNSET
        }
        config = resolve_legacy(
            "ServeEngine", config, legacy, ServeConfig, _serve_config_from_legacy
        )
        self.config = config
        self.max_batch = config.max_batch
        self.queue_depth = config.queue_depth
        self.max_wait_us = config.max_wait_us
        self.buckets = config.buckets
        self.overflow = config.backpressure
        self.shards = config.shards
        self._runners: dict[str, _ModelRunner] = {}
        self._lock = threading.Lock()

    # -- registry ------------------------------------------------------
    def register(
        self,
        name: str,
        design: CompiledDesign | str | Path,
        warmup: bool = False,
    ) -> CompiledDesign:
        """Register a design (or load one from an artifact path)."""
        if not isinstance(design, CompiledDesign):
            design = load_design(design)
        runner = _ModelRunner(
            name, design, self.max_batch, self.queue_depth,
            self.max_wait_us, self.buckets, self.shards, config=self.config,
        )
        with self._lock:
            if name in self._runners:
                # never replace silently: two designs would be mixed under
                # one name.  Version rollout lives in flow.Deployment.
                raise ValueError(
                    f"model {name!r} already registered (roll a new version "
                    "via repro.flow.Deployment.register(..., version=))"
                )
            self._runners[name] = runner
        try:
            if warmup:
                runner.warmup()
            runner.start()
        except BaseException:  # failed warmup/start must not leave a dead entry
            with self._lock:
                self._runners.pop(name, None)
            raise
        get_registry().set_gauge("serve_cmvm_dot_share", design.dot_share, model=name)
        return design

    def unregister(self, name: str, timeout: float = 5.0) -> None:
        """Drop a model after draining its queues (waiting up to
        ``timeout`` seconds for the dispatchers to finish; requests
        still queued after that are failed loudly, never left hanging)."""
        with self._lock:
            runner = self._runners.pop(name)
        runner.stop(timeout)

    def models(self) -> list[str]:
        with self._lock:
            return sorted(self._runners)

    def _runner(self, name: str) -> _ModelRunner:
        try:
            return self._runners[name]
        except KeyError:
            raise KeyError(f"model {name!r} is not registered") from None

    # -- serving -------------------------------------------------------
    def _validate(self, name: str, runner: _ModelRunner, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x)
        if x.shape != runner.in_shape:
            raise ValueError(
                f"model {name!r} expects one sample of shape {runner.in_shape}, "
                f"got {x.shape}"
            )
        if not np.issubdtype(x.dtype, np.integer):
            raise TypeError(
                f"model {name!r} expects integer-grid samples, got dtype "
                f"{x.dtype} (quantize floats with the design's in_quant first)"
            )
        return x

    def submit(self, name: str, x: np.ndarray, deadline_s: float | None = None) -> Future:
        """Enqueue one sample (integer grid, shape ``in_shape``).

        ``deadline_s`` (relative seconds; default
        ``ServeConfig.deadline_ms``) bounds how long the request may
        wait for dispatch — on expiry the Future fails with
        :class:`DeadlineExceededError` instead of executing dead work.

        May raise :class:`QueueFullError` (reject policy, queue at
        capacity), :class:`EngineClosedError` (the submit raced
        ``unregister``/``shutdown``; under a :class:`repro.flow.Deployment`
        rollout the deployment layer retries onto the new version),
        :class:`ShardCrashedError` (dispatch lane died mid-enqueue) or
        :class:`ModelUnhealthyError` (restart budget exhausted)."""
        runner = self._runner(name)
        x = self._validate(name, runner, x)
        t_submit = time.perf_counter()
        return runner.submit_one(
            x, t_submit, block=self.overflow != "reject",
            deadline=runner.deadline_abs(t_submit, deadline_s),
        )

    def submit_batch(self, name: str, xs, deadline_s: float | None = None) -> list[Future]:
        """Enqueue many samples at once; returns one Future per sample.

        Amortizes per-request overhead (registry lookup, validation,
        clock read, shard lock) across the batch — the high-throughput
        entrypoint for clients that already hold several requests.
        ``xs`` is an iterable of samples or an ``[n, *in_shape]`` array;
        chunks are spread across shards.  ``deadline_s`` applies to
        every sample in the batch (see ``submit``).

        Backpressure mirrors ``submit`` per sample, except that with the
        "reject" policy an overflowing sample's Future is *failed* with
        :class:`QueueFullError` (and counted) instead of raising, so one
        full queue cannot lose the whole batch; samples cut off by a
        racing shutdown are failed with :class:`EngineClosedError` (or
        :class:`ShardCrashedError` if the lane died).  Every returned
        Future resolves.
        """
        runner = self._runner(name)
        xs = [self._validate(name, runner, x) for x in xs]
        t_submit = time.perf_counter()
        return runner.submit_many(
            xs, t_submit, block=self.overflow != "reject",
            deadline=runner.deadline_abs(t_submit, deadline_s),
        )

    def infer(
        self,
        name: str,
        x: np.ndarray,
        timeout: float | None = 30.0,
        deadline_s: float | None = None,
    ):
        """Synchronous single-sample convenience wrapper.

        The client ``timeout`` is tied into the deadline path: unless a
        deadline is configured or passed explicitly, the request carries
        ``deadline_s=timeout``, so work abandoned by an expired
        ``result(timeout)`` is *shed* by the dispatcher instead of
        executed into a slab slot nobody is waiting on.  Client-side
        expiries are counted in ``stats()["n_client_timeouts"]``.
        """
        if deadline_s is None:
            dms = self.config.deadline_ms
            deadline_s = dms * 1e-3 if dms is not None else timeout
        fut = self.submit(name, x, deadline_s=deadline_s)
        try:
            return fut.result(timeout)
        except FutureTimeoutError:
            try:
                self._runner(name).count_client_timeout()
            except KeyError:
                pass  # model unregistered while we waited
            raise

    def warmup(self, name: str) -> float:
        return self._runner(name).warmup()

    def stats(self, name: str | None = None) -> dict:
        if name is not None:
            return self._runner(name).stats()
        with self._lock:
            runners = list(self._runners.items())
        return {n: r.stats() for n, r in runners}

    def metrics_text(self) -> str:
        """Prometheus text exposition (format 0.0.4) over every model.

        Families are derived from the live runners — request/batch/reject
        counters, per-shard queue-depth gauges, per-bucket hit counters,
        per-stage wall totals and µs histograms, latency-percentile
        gauges, and the resilience families (shed/fast-fail/fallback/
        client-timeout counters, breaker state and trip counts, restart
        counts, health gauge) — so scraping this endpoint and reading
        ``stats()`` can never disagree.  Process-wide solver/compiler
        counters live in ``repro.obs.metrics.get_registry()`` (exposed
        by ``benchmarks/run.py obs``), not here, to avoid double
        counting.
        """
        with self._lock:
            runners = list(self._runners.items())
        req, batches, rejected, qd, bucket, jit = [], [], [], [], [], []
        stage_tot, stage_hist, lat = [], [], []
        shed, fastf, fallb, ctime = [], [], [], []
        brk_state, brk_trips, restarts, healthy = [], [], [], []
        _BRK_STATE = {"closed": 0, "half_open": 1, "open": 2}
        for name, r in runners:
            s = r.stats()
            m = {"model": name}
            req.append((m, s["n_requests"]))
            batches.append((m, s["n_batches"]))
            rejected.append((m, s["n_rejected"]))
            jit.append((m, s["n_jit_compiles"]))
            shed.append((m, s["n_shed"]))
            fastf.append((m, s["n_fast_failed"]))
            fallb.append((m, s["n_fallback_batches"]))
            ctime.append((m, s["n_client_timeouts"]))
            brk_state.append((m, _BRK_STATE.get(s["breaker"]["state"], -1)))
            brk_trips.append((m, s["breaker"]["n_trips"]))
            restarts.append((m, s["supervision"]["n_restarts"]))
            healthy.append((m, int(s["supervision"]["healthy"])))
            for snap in s["shards"]:
                qd.append(
                    ({"model": name, "shard": snap["shard"]}, snap["queue_depth"])
                )
            for b, c in s["bucket_hits"].items():
                bucket.append(({"model": name, "bucket": b}, c))
            for st in StageAccumulator.STAGES:
                stage_tot.append(
                    ({"model": name, "stage": st}, s["per_stage"][st]["total_ms"] / 1e3)
                )
                stage_hist.append(
                    (
                        {"model": name, "stage": st},
                        Histogram.merged(sh.stage_hist[st] for sh in r.shards),
                    )
                )
            if s["n_latency_samples"]:
                for q in ("p50", "p99"):
                    lat.append(({"model": name, "quantile": q}, s[f"{q}_ms"]))
        families = [
            ("serve_requests_total", "counter", "requests completed", req),
            ("serve_batches_total", "counter", "batches dispatched", batches),
            ("serve_rejected_total", "counter",
             "requests rejected by backpressure", rejected),
            ("serve_shed_total", "counter",
             "requests shed on an expired deadline", shed),
            ("serve_fast_failed_total", "counter",
             "requests failed fast by an open circuit breaker", fastf),
            ("serve_fallback_batches_total", "counter",
             "batches served by the interpreter fallback", fallb),
            ("serve_client_timeouts_total", "counter",
             "infer() client-side result timeouts", ctime),
            ("serve_breaker_state", "gauge",
             "circuit breaker state (0=closed 1=half_open 2=open)", brk_state),
            ("serve_breaker_trips_total", "counter",
             "circuit breaker closed->open transitions", brk_trips),
            ("serve_restarts_total", "counter",
             "dispatcher threads restarted by supervision", restarts),
            ("serve_healthy", "gauge",
             "1 while the model serves, 0 once escalated unhealthy", healthy),
            ("serve_queue_depth", "gauge", "queued requests per shard", qd),
            ("serve_bucket_hits_total", "counter",
             "batches dispatched per bucket shape", bucket),
            ("serve_jit_compiled_buckets", "gauge",
             "bucket shapes jit-compiled so far", jit),
            ("serve_stage_seconds_total", "counter",
             "wall seconds charged per dispatch stage", stage_tot),
            ("serve_stage_us", "histogram",
             "per-stage wall microseconds per batch (queue_wait: per request)",
             stage_hist),
            ("serve_latency_ms", "gauge",
             "end-to-end latency percentiles", lat),
        ]
        return render_prometheus(families)

    # -- lifecycle -----------------------------------------------------
    def shutdown(self, timeout: float = 5.0) -> None:
        """Stop all dispatchers after draining their queues."""
        with self._lock:
            runners = list(self._runners.values())
            self._runners.clear()
        for r in runners:
            r.stop(timeout)

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
