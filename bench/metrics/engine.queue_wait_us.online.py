"""Mean time a request waited in the engine's queue before its batch was
formed (``Deployment.stats()["per_stage"]["queue_wait"]``, host clock),
in us, over the requests of the window."""


def read(rec):
    if rec.stage_s is None or not rec.stage_s["queue_wait"][1]:
        return None
    seconds, count = rec.stage_s["queue_wait"]
    return seconds / count * 1e6
