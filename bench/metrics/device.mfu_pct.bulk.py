"""The whole step's share of the chip's peak, in %: the network's ops
per event (``bench/harness/work.py``) times the events per second of
the traced run, over the int8 peak."""

from bench.harness import work


def read(rec):
    t = rec.trace
    if t is None or not t.n_devices or rec.seconds <= 0:
        return None
    rate = work.ops_per_event(rec.cell.config, rec.cell.root) * rec.events / rec.seconds
    return rate / rec.peak()["int8_ops_per_s"] * 100.0
