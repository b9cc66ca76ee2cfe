"""The least bytes of device memory a query has to read, or an index build
has to move, at the columns' logical widths, from the lake's row counts.

These are lower bounds on traffic, not models of an implementation: any
plan that answers the query reads each needed value at least once, so the
count is the same whatever runs it. A roofline share built on them is the
least time (bytes over the chip's peak bandwidth) over the measured device
time, and cannot pass 100% unless the device time leaves out work.
"""

from __future__ import annotations

# Logical widths in bytes: int64 keys and float64 decimals are 8 bytes,
# date32 and int32 are 4.
WIDTH = {"int64": 8, "float64": 8, "date32": 4, "int32": 4}

# Q3 without the customer join reads, from the covering indexes:
# li_idx: l_orderkey (int64), l_extendedprice, l_discount (float64) and
# l_shipdate (date32) of every lineitem row (the shipdate filter needs all
# of them and keeps about half);
# od_idx: o_orderkey (int64), o_orderdate (date32), o_shippriority (int32)
# of every order row (the orderdate filter needs all of them).
Q3_LINEITEM = ("int64", "float64", "float64", "date32")
Q3_ORDERS = ("int64", "date32", "int32")

# The lineitem covering index the build cell creates: l_orderkey (int64)
# indexed; l_extendedprice, l_discount (float64) and l_shipdate (date32)
# included.
BUILD_LINEITEM = ("int64", "float64", "float64", "date32")


def q3(lineitem_rows: int, orders_rows: int) -> int:
    """Q3's least bytes read: every row of the columns above, once."""
    return (lineitem_rows * sum(WIDTH[t] for t in Q3_LINEITEM)
            + orders_rows * sum(WIDTH[t] for t in Q3_ORDERS))


def build(rows: int) -> int:
    """An index build's least bytes: every indexed and included value of
    the source read once and written once, in whatever order."""
    return 2 * rows * sum(WIDTH[t] for t in BUILD_LINEITEM)
