"""Mean time of an index delete, in ms, on the host clock: the log's
ACTIVE -> DELETING -> DELETED transition."""

import cycle_time


def read(w):
    return cycle_time.mean_ms(w, "delete")
