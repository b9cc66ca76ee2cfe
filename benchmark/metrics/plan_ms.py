"""Planning time per query, in ms: the program's ``plan.normalize``,
``optimize.join_reorder`` and ``rewrite.index_rules`` spans (Session.optimize
and the index rules), summed per query and averaged over the window's
traced queries. Nothing to read without the program's spans."""

PLAN_SPANS = ("plan.normalize", "optimize.join_reorder",
              "rewrite.index_rules")


def read(w):
    traced = [s for s in w.sent if s.spans]
    if not traced:
        return None
    total = sum(b - a for s in traced for (name, a, b) in s.spans
                if name in PLAN_SPANS)
    return total / len(traced) * 1000.0
