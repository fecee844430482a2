"""Device idle share of the traced window, in percent: 1 - (union of the
device's operation intervals) / window, the mean over the chips used
(each chip's value is logged on an earlier line)."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
