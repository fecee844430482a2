"""exchange_exposed_ms: per flush, the device time of collective
operations (all-gather, reduce-scatter, all-to-all, all-reduce) during
which no other operation runs on that chip; the mean over chips, in ms.
Reads nothing where no collective ran."""


def read(run):
    tr, r = run.trace, run.records
    if tr is None or not r.get("flushes"):
        return None
    per = [tr.exposed_collective_s(c) for c in tr.chips()]
    per = [x for x in per if x is not None]
    if not per:
        return None
    return 1e3 * sum(per) / len(per) / len(r["flushes"])
