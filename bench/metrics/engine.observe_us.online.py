"""Mean time the engine's dispatcher spends on a batch's bookkeeping
after its futures resolved (``per_stage["observe"]``: flight records,
the queue-wait histogram and the registry gauges), host clock, in us,
over the batches of the window."""


def read(rec):
    if rec.stage_s is None or not rec.stage_s.get("observe", (0.0, 0))[1]:
        return None
    seconds, count = rec.stage_s["observe"]
    return seconds / count * 1e6
