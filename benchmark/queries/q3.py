"""TPC-H Q3 (spec 2.4.3) without the customer join: the shipping-priority
query over lineitem and orders, top 10 unshipped orders by revenue.

Literal set: ``{"date": "1995-03-15"}``, the spec's validation date.
The customer table and its ``c_mktsegment`` filter are left out; that is
the form the engine runs through its covering-index join.
"""

from __future__ import annotations

import datetime

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ORDERED = True
COLUMNS = ["l_orderkey", "o_orderdate", "o_shippriority", "revenue"]


def build(session, lake, spec: dict, lits: dict):
    from hyperspace_tpu.plan.expr import col, sum_

    cutoff = datetime.date.fromisoformat(lits["date"])
    li = session.read.parquet(lake.dirs["lineitem"])
    od = session.read.parquet(lake.dirs["orders"])
    return (li.filter(col("l_shipdate") > cutoff)
            .join(od.filter(col("o_orderdate") < cutoff),
                  on=col("l_orderkey") == col("o_orderkey"))
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(sum_(col("l_extendedprice") * (1 - col("l_discount")))
                 .alias("revenue"))
            .sort(("revenue", False), "o_orderdate")
            .limit(10))


def reference(lake, spec: dict, lits: dict, dtype=np.float64) -> pa.Table:
    """The same query in numpy on the host, from the parquet files.
    Revenue is computed and summed in ``dtype``."""
    cutoff = datetime.date.fromisoformat(lits["date"])
    li = pq.read_table(lake.dirs["lineitem"],
                       columns=["l_orderkey", "l_extendedprice",
                                "l_discount"],
                       filters=[("l_shipdate", ">", cutoff)])
    od = pq.read_table(lake.dirs["orders"],
                       columns=["o_orderkey", "o_orderdate",
                                "o_shippriority"],
                       filters=[("o_orderdate", "<", cutoff)])
    okey = od.column("o_orderkey").to_numpy()
    by_key = np.argsort(okey, kind="stable")
    okey = okey[by_key]
    odate = od.column("o_orderdate").cast(pa.int32()).to_numpy()[by_key]
    oprio = od.column("o_shippriority").to_numpy()[by_key]

    lkey = li.column("l_orderkey").to_numpy()
    pos = np.minimum(np.searchsorted(okey, lkey), max(len(okey) - 1, 0))
    hit = (okey[pos] == lkey) if len(okey) else np.zeros(len(lkey), bool)
    lkey, pos = lkey[hit], pos[hit]
    price = li.column("l_extendedprice").to_numpy()[hit].astype(dtype)
    disc = li.column("l_discount").to_numpy()[hit].astype(dtype)
    rev = price * (dtype(1) - disc)

    order = np.argsort(lkey, kind="stable")
    lkey, pos, rev = lkey[order], pos[order], rev[order]
    starts = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]]) \
        if len(lkey) else np.zeros(0, np.int64)
    sums = np.add.reduceat(rev, starts, dtype=dtype) if len(starts) \
        else np.zeros(0, dtype)
    gkey, gpos = lkey[starts], pos[starts]
    top = np.lexsort((odate[gpos], -sums.astype(np.float64)))[:10]
    return pa.table({
        "l_orderkey": pa.array(gkey[top]),
        "o_orderdate": pa.array(odate[gpos][top], pa.int32())
        .cast(pa.date32()),
        "o_shippriority": pa.array(oprio[gpos][top]),
        "revenue": pa.array(sums[top].astype(np.float64)),
    })
