"""The device's idle share (%) of the traced window: 1 - (union of the
device's operation intervals) / window, from the profiler trace."""


def read(w):
    if w.trace is None or w.trace["idle_share"] is None:
        return None
    return 100.0 * w.trace["idle_share"]
