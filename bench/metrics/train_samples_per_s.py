"""train_samples_per_s: samples of the train steps dispatched in the window,
over the window, which ends when the last step's params are ready (host
clock)."""


def read(run):
    r = run.records
    if "steps" not in r or r["window_s"] <= 0:
        return None
    return r["samples"] / r["window_s"]
