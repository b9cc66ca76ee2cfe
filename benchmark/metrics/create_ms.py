"""Mean time of an index create, in ms, on the host clock: the public
``create_index`` call, from the parquet read to the last bucket file and
the log entry."""

import cycle_time


def read(w):
    return cycle_time.mean_ms(w, "create")
