"""The SPMD tier's host time per query, in ms: the program's
``spmd.gate`` spans (the decision to go SPMD) and ``spmd.dispatch`` spans
(leaf preparation, launch and merge), less the time inside them that the
host spent blocked on the device (``device.sync`` spans), averaged over
the window's traced queries. Nothing to read without ``spmd.gate``."""

import span_time


def read(w):
    if not any(span_time.spans(s, ("spmd.gate",))
               for s in span_time.traced(w)):
        return None
    return span_time.mean_ms(w, ("spmd.gate", "spmd.dispatch"),
                             less=("device.sync",))
