"""How late the open loop sent its requests: p99 of send time minus due
time, in ms, over every request of the window.  A high value means the
generator, which shares the process and its interpreter lock with the
engine, was starved, and that the tail is partly the client's."""

import numpy as np


def read(rec):
    if rec.lag_s is None or len(rec.lag_s) == 0:
        return None
    return float(np.percentile(rec.lag_s, 99)) * 1e3
