"""Blocking device-to-host round trips per query: the program's
``device.sync`` spans, one per round trip, counted over the window's
traced queries."""

import span_time


def read(w):
    return span_time.mean_count(w, ("device.sync",))
