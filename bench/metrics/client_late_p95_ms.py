"""client_late_p95_ms: 95th percentile of how late the load generator
submitted each query after it was due (benchmark clock). It is high
wherever a flush ran when a query fell due, since the session serves in
the caller's thread."""
import numpy as np


def read(run):
    late = run.records.get("late_ms")
    return None if late is None else float(np.percentile(late, 95))
