"""Mean time of the engine's blocking device call per batch
(``per_stage["dispatch"]``: host to device copy, the jitted
``forward_int``, device to host copy), host clock, in us, over the
batches of the window."""


def read(rec):
    if rec.stage_s is None or not rec.stage_s["dispatch"][1]:
        return None
    seconds, count = rec.stage_s["dispatch"]
    return seconds / count * 1e6
