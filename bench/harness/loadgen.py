"""The load generator: an open loop of arrivals, and a closed loop of calls.

Open loop.  ``schedule`` draws a fixed number of arrivals, rate x
seconds, as a Poisson process whose gaps are scaled to span exactly the
window, so every seed offers the same work in another order.
``open_loop`` sends each request when it is due, whatever happened to
earlier ones, and times it from its due time to the completion of its
future: a stall of the server or of the generator itself shows in the
latency of every request it delays.  How late each request was sent
is kept apart, so that a starved generator is not read as a fast server.

Closed loop.  ``closed_loop`` is one client that calls, waits for the
result on the host, and calls again, for the length of the window.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np


def schedule(rate_per_s: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times, in seconds from the window's start, of round(rate x
    seconds) Poisson arrivals scaled to span [0, seconds)."""
    n = max(1, round(rate_per_s * seconds))
    t = np.cumsum(rng.exponential(size=n + 1))
    return (t[:-1] - t[0]) / (t[-1] - t[0]) * seconds


@dataclass
class OpenLoopRun:
    due: np.ndarray  # seconds from the window's start
    sent: np.ndarray  # seconds from the window's start
    done: np.ndarray  # seconds from the window's start; nan: no answer came
    failed: np.ndarray  # bool: the submit or the future raised
    outputs: np.ndarray  # one answer per request (undefined where none came)
    seconds: float  # from the window's start until every request settled

    @property
    def latency_s(self) -> np.ndarray:
        """Due time to answer; inf for a request that failed or never came."""
        lat = self.done - self.due
        return np.where(self.failed | np.isnan(lat), np.inf, lat)

    @property
    def lag_s(self) -> np.ndarray:
        return self.sent - self.due


class _Settle:
    """Done-callback of request ``i``: keep its answer and the time."""

    __slots__ = ("clock", "done", "failed", "i", "outputs")

    def __init__(self, i, outputs, failed, done, clock):
        self.i, self.outputs, self.failed, self.done, self.clock = i, outputs, failed, done, clock

    def __call__(self, fut) -> None:
        try:
            self.outputs[self.i] = fut.result()
        except Exception:  # the request failed: counted, never compared
            self.failed[self.i] = True
        self.done[self.i] = self.clock()


def open_loop(submit, rows, due: np.ndarray, out_shape: tuple, timeout_s: float = 60.0,
              clock=time.perf_counter, sleep=time.sleep) -> OpenLoopRun:
    """Send ``rows(i)`` through ``submit`` (which returns a Future) at
    ``due[i]`` seconds after the start, sleeping, never spinning, in
    between; wait for every answer, at most ``timeout_s`` past the last
    due time.  Times are kept on ``clock`` and returned relative to the
    start."""
    n = len(due)
    sent, done = np.empty(n), np.full(n, np.nan)
    failed, outputs = np.zeros(n, bool), np.zeros((n, *out_shape), np.int32)
    t0 = clock()
    for i in range(n):
        rem = t0 + due[i] - clock()
        if rem > 0:
            sleep(rem)
        sent[i] = clock()
        try:
            fut = submit(rows(i))
        except Exception:  # refused at the door: a failed request
            failed[i] = True
            done[i] = clock()
            continue
        fut.add_done_callback(_Settle(i, outputs, failed, done, clock))
    deadline = t0 + due[-1] + timeout_s
    while np.isnan(done).any() and clock() < deadline:
        sleep(1e-3)
    seconds = clock() - t0
    # copies: an answer that comes after the deadline changes nothing here
    return OpenLoopRun(due, sent - t0, done - t0, failed.copy(), outputs.copy(), seconds)


@dataclass
class ClosedLoopRun:
    seconds: float
    offsets: list  # pool offset of each call
    outputs: list  # host array of each call
    events: int


def closed_loop(call, pool: np.ndarray, chunk: int, offsets: np.ndarray,
                seconds: float, clock=time.perf_counter) -> ClosedLoopRun:
    """Call ``call(pool[o:o + chunk])`` with o = offsets[k] in turn,
    waiting for each host result, until ``seconds`` have passed."""
    outs, used = [], []
    t0 = clock()
    k = 0
    while clock() - t0 < seconds:
        o = int(offsets[k % len(offsets)])
        outs.append(np.asarray(call(pool[o : o + chunk])))
        used.append(o)
        k += 1
    return ClosedLoopRun(clock() - t0, used, outs, k * chunk)
