"""Broadcast joins probed through a position table, per query: the
program's ``join.probe_table`` spans, one per direct-address table built,
counted over the window's traced queries. Nothing to read where every
join searches, or the program has no such probe."""

import span_time


def read(w):
    return span_time.mean_count(w, ("join.probe_table",))
