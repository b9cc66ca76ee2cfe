"""Device busy time per query, in ms: the union of the device's operation
intervals in the traced window over the queries completed in it."""


def read(w):
    done = sum(1 for s in w.sent if s.error is None)
    if w.trace is None or not done or w.trace["busy_s"] <= 0:
        return None
    return 1000.0 * w.trace["busy_s"] / done
