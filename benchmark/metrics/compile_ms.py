"""XLA compiles and compile-cache loads per query, in ms: the program's
``xla.compile`` spans, averaged over the window's traced queries; 0 in a
window without one. Nothing to read from a program that records no
``result.fetch`` span, since it records no compile span either."""

import span_time


def read(w):
    if span_time.mean_count(w, ("result.fetch",)) is None:
        return None
    return span_time.mean_ms(w, ("xla.compile",)) or 0.0
