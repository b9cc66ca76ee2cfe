"""Source rows indexed per second: the source rows of every create of the
window's completed cycles, over those cycles' summed step time (create,
delete and vacuum; the harness's reads between steps left out)."""

import cycle_time


def read(w):
    done = cycle_time.done(w)
    step_s = sum(c.step_s for c in done)
    if not done or step_s <= 0:
        return None
    return sum(c.record.get("source_rows", 0) for c in done) / step_s
