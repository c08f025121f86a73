"""The 95th percentile of request latency, in ms, over every request of
the window: each timed from when it was due to its answer, a failed one
as waiting the whole run.  It is the tail a trigger path feels, and on
a host that stands still for a tenth of a second a few times a minute
it reads either a few ms or tens of ms, by whether a stall fell into
the window; so it is read here, beside the steadier median that the
cell holds end to end."""

import numpy as np


def read(rec):
    if rec.latency_s is None or len(rec.latency_s) == 0:
        return None
    return float(np.percentile(rec.latency_s, 95)) * 1e3
