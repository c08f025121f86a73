"""The open-loop generator times requests from when they were due."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench.harness import loadgen


def test_schedule_is_a_fixed_amount_of_work_in_the_window():
    a = loadgen.schedule(1000, 2.0, np.random.default_rng(1))
    b = loadgen.schedule(1000, 2.0, np.random.default_rng(2))
    assert len(a) == len(b) == 2000
    for due in (a, b):
        assert due[0] == 0.0 and due[-1] < 2.0
        assert np.all(np.diff(due) >= 0)
    assert not np.array_equal(a, b)


class _StallingServer:
    """Answers each request on one worker thread; stalls once, for
    ``stall_s``, when it reaches request ``at``."""

    def __init__(self, at, stall_s, stall_in_submit=False):
        self.at, self.stall_s, self.stall_in_submit = at, stall_s, stall_in_submit
        self.n = 0
        self.queue = []
        self.cv = threading.Condition()
        self.submit_s = []  # server-side time from submit to answer
        self.closed = False
        self.worker = threading.Thread(target=self._serve, daemon=True)
        self.worker.start()

    def submit(self, x):
        i, self.n = self.n, self.n + 1
        if self.stall_in_submit and i == self.at:
            time.sleep(self.stall_s)  # e.g. a blocked enqueue
        fut = Future()
        with self.cv:
            self.queue.append((i, x, fut, time.perf_counter()))
            self.cv.notify()
        return fut

    def _serve(self):
        while True:
            with self.cv:
                while not self.queue and not self.closed:
                    self.cv.wait(0.01)
                if self.closed and not self.queue:
                    return
                i, x, fut, t_submit = self.queue.pop(0)
            if not self.stall_in_submit and i == self.at:
                time.sleep(self.stall_s)
            fut.set_result(np.full(2, x, np.int32))
            self.submit_s.append(time.perf_counter() - t_submit)

    def close(self):
        with self.cv:
            self.closed = True
        self.worker.join(5)
        assert not self.worker.is_alive()


@pytest.mark.parametrize("stall_in_submit", [False, True])
def test_stall_shows_in_latency_from_due_time(stall_in_submit):
    stall = 0.2
    due = np.arange(200) * 0.002  # 500 requests/s for 0.4 s
    server = _StallingServer(at=50, stall_s=stall, stall_in_submit=stall_in_submit)
    try:
        run = loadgen.open_loop(server.submit, lambda i: i, due, (2,), timeout_s=5.0)
    finally:
        server.close()
    assert not run.failed.any() and np.isfinite(run.latency_s).all()
    np.testing.assert_array_equal(run.outputs[:, 0], np.arange(200))
    # the requests due during the stall waited for it
    assert run.latency_s.max() >= 0.8 * stall
    assert np.percentile(run.latency_s, 90) >= 0.3 * stall
    if stall_in_submit:
        # the generator itself was held: it sent late, and the lag says so,
        # while the server timed every request from its late submit
        assert run.lag_s.max() >= 0.8 * stall
        assert max(server.submit_s) < 0.5 * stall


def test_failed_and_refused_requests_count_as_failed():
    def submit(x):
        if x == 3:
            raise RuntimeError("queue full")
        fut = Future()
        if x == 5:
            fut.set_exception(RuntimeError("shed"))
        else:
            fut.set_result(np.zeros(1, np.int32))
        return fut

    run = loadgen.open_loop(submit, lambda i: i, np.zeros(8), (1,), timeout_s=1.0)
    assert run.failed.tolist() == [i in (3, 5) for i in range(8)]
    assert np.isinf(run.latency_s[[3, 5]]).all()


def test_missing_answer_is_not_waited_for_past_the_timeout():
    futs = []

    def submit(x):
        futs.append(Future())
        if x == 0:
            futs[-1].set_result(np.zeros(1, np.int32))
        return futs[-1]

    t = time.perf_counter()
    run = loadgen.open_loop(submit, lambda i: i, np.zeros(2), (1,), timeout_s=0.2)
    assert time.perf_counter() - t < 2.0
    assert np.isnan(run.done[1]) and np.isinf(run.latency_s[1])


def test_closed_loop_calls_until_the_window_closes():
    pool = np.arange(40).reshape(20, 2)
    offsets = np.array([0, 5, 10])
    run = loadgen.closed_loop(lambda x: x * 2, pool, 4, offsets, seconds=0.05)
    assert len(run.outputs) >= 1 and run.events == 4 * len(run.outputs)
    for o, y in zip(run.offsets, run.outputs):
        np.testing.assert_array_equal(y, pool[o : o + 4] * 2)
    assert run.seconds >= 0.05
