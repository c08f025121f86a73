"""Share of its roofline that the ``jit_forward_int`` executable reaches,
in %: the least time the chip could take for the calls it ran, the
larger of their ops over the int8 peak and their bytes over HBM
bandwidth (``bench/harness/work.py``), over their device time."""

from bench.harness import work

MODULE = "jit_forward_int"


def read(rec):
    t = rec.trace
    if t is None or not t.n_devices or not t.module_n.get(MODULE):
        return None
    least, _bound = work.least_time_s(
        rec.cell.config, rec.chunk, rec.in_itemsize, rec.peak(), rec.cell.root
    )
    return least * t.module_n[MODULE] / t.module_s[MODULE] * 100.0
