"""serve_p95_ms: 95th percentile of the latencies of all queries due in
the window (host clock, open loop; see serve_p50_ms)."""
import numpy as np


def read(run):
    lat = run.records.get("latency_ms")
    return None if lat is None else float(np.percentile(lat, 95))
