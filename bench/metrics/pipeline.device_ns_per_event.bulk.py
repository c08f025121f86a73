"""Device time of the ``jit_forward_int`` executable in the trace, in ns,
divided by the events of the traced window."""

MODULE = "jit_forward_int"


def read(rec):
    t = rec.trace
    if t is None or not t.n_devices or MODULE not in t.module_s or not rec.events:
        return None
    return t.module_s[MODULE] / rec.events * 1e9
