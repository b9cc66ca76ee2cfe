"""Source checks inside the index rewrite, per query, in ms: the
program's ``rewrite.source_check`` spans (listing the source's files,
their stat and the signature compared with the index's) and
``rewrite.index_lookup`` spans (the index manager's read of the ACTIVE
indexes), averaged over the window's traced queries."""

import span_time


def read(w):
    return span_time.mean_ms(w, ("rewrite.source_check",
                                 "rewrite.index_lookup"))
