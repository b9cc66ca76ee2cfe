"""The answer's fetch per query, in ms: the program's ``result.fetch``
spans (the device-to-host copy of the answer and its Arrow build, inside
the query's root span), averaged over the window's traced queries."""

import span_time


def read(w):
    return span_time.mean_ms(w, ("result.fetch",))
