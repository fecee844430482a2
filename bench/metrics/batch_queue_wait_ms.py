"""batch_queue_wait_ms: mean over all queries of the time from when each
was due to the start of the submit() or poll() call that flushed it
(benchmark clock)."""
import numpy as np


def read(run):
    w = run.records.get("queue_wait_ms")
    return None if w is None else float(np.mean(w))
