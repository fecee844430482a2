"""batcher_wait_ms: mean over the window's queries of the batcher's own
wait, from each query's arrival stamp in `submit()` to the drain that
flushed it (program counter ``serve_batch_wait_ms``), in ms. Unlike
`batch_queue_wait_ms` it holds none of the client's lateness.

The counter also holds set-up's warm-up queries, which are submitted and
flushed on an injected clock at one instant and so add 0 to the sum: the
window's mean is the sum over the count less those queries."""
import program_obs


def read(run):
    h = program_obs.histogram("serve_batch_wait_ms")
    if h is None or run.records.get("loop") != "open":
        return None
    n = h["count"] - sum(run.spec.traffic.get("warm_queries", ()))
    return h["sum"] / n if n > 0 else None
