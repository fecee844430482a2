"""serve_samples_per_s: samples whose probabilities were returned, over the
window from its start to the last answer (host clock, closed loop)."""


def read(run):
    r = run.records
    if r.get("loop") != "closed" or r["window_s"] <= 0:
        return None
    return r["samples"] / r["window_s"]
