"""Queries completed per second over the window: the queries that ended
without an error, over the time from the window's start to the first
completion after ``--seconds``."""


def read(w):
    done = sum(1 for s in w.sent if s.error is None)
    return done / (w.t1 - w.t0)
