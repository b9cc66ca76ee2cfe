"""Mean time of an index vacuum, in ms, on the host clock: the log's
DELETED -> VACUUMING -> DOESNOTEXIST transition and the removal of every
version directory's files."""

import cycle_time


def read(w):
    return cycle_time.mean_ms(w, "vacuum")
