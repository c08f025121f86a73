"""Device time of the ``jit_forward_int`` executable in the trace, in us,
divided by the batches the engine dispatched in the traced window."""

MODULE = "jit_forward_int"


def read(rec):
    t = rec.trace
    if t is None or not t.n_devices or MODULE not in t.module_s or not rec.batches:
        return None
    return t.module_s[MODULE] / rec.batches * 1e6
