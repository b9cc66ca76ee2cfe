"""An index build's share (%) of the HBM roofline: the least bytes a build
must move (least_bytes.build, from the source's rows) over the chip's peak
HBM bandwidth (peaks.py), divided by the device time per create.
Bandwidth bounds it: hashing, sorting and gathering do a few operations
per byte."""

import cycle_time
import least_bytes
import peaks


def read(w):
    done = [c for c in cycle_time.done(w) if "source_rows" in c.record]
    n = cycle_time.creates(w)
    if w.trace is None or not done or not n or w.trace["busy_s"] <= 0:
        return None
    rows = done[0].record["source_rows"]
    least_s = least_bytes.build(rows) \
        / peaks.peak(w.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (w.trace["busy_s"] / n)
