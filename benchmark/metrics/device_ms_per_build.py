"""Device busy time per index create, in ms: the union of the device's
operation intervals in the traced window over the creates completed in
it. Delete, vacuum and the harness's reads run nothing on the device."""

import cycle_time


def read(w):
    n = cycle_time.creates(w)
    if w.trace is None or not n or w.trace["busy_s"] <= 0:
        return None
    return 1000.0 * w.trace["busy_s"] / n
