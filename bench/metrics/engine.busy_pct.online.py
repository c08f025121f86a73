"""Share of the window in which the engine's dispatcher threads worked
rather than waited for requests or for a batch to fill: the ``pad``,
``dispatch``, ``copy_out`` and ``observe`` stages (``per_stage``, host
clock) over the window times the shards of the cell's engine, in %."""

WORK = ("pad", "dispatch", "copy_out", "observe")


def read(rec):
    if rec.stage_s is None or any(s not in rec.stage_s for s in WORK):
        return None
    shards = rec.cell.traffic["serve"].get("shards", 1)
    return 100.0 * sum(rec.stage_s[s][0] for s in WORK) / (rec.seconds * shards)
