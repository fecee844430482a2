"""batch_occupancy: queries flushed over flushes times the micro-batch
capacity, in percent (benchmark's count of each call that flushed)."""


def read(run):
    r = run.records
    fl = r.get("flushes")
    if r.get("loop") != "open" or not fl:
        return None
    return 100.0 * sum(fl) / (len(fl) * r["max_batch_queries"])
