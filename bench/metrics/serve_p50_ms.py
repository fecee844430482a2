"""serve_p50_ms: median latency of all queries due in the window, from
when each was due to when the session returned its probabilities (host
clock, open loop)."""
import numpy as np


def read(run):
    lat = run.records.get("latency_ms")
    return None if lat is None else float(np.percentile(lat, 50))
