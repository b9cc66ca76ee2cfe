"""The data-skipping probe per query, in ms: the program's
``skipping.probe`` spans (the sketch table loaded or hit, and the filter
evaluated over it), averaged over the window's traced queries."""

import span_time


def read(w):
    return span_time.mean_ms(w, ("skipping.probe",))
