"""Q3's share (%) of the HBM roofline: the least bytes Q3 must read
(least_bytes.q3, from the lake's row counts) over the chip's peak HBM
bandwidth (peaks.py), divided by the device time per query. Bandwidth
bounds it: Q3 does a few operations per byte."""

import least_bytes
import peaks


def read(w):
    done = sum(1 for s in w.sent if s.error is None
               and s.query.kind == "q3")
    if w.trace is None or not done or w.trace["busy_s"] <= 0:
        return None
    least_s = least_bytes.q3(w.lake.rows["lineitem"], w.lake.rows["orders"]) \
        / peaks.peak(w.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (w.trace["busy_s"] / done)
