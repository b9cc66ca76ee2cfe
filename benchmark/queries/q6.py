"""TPC-H Q6 (spec 2.4.6), the forecasting revenue change query: the sum of
``l_extendedprice * l_discount`` over the lineitems shipped in one year,
with a discount within 0.01 of DISCOUNT and a quantity under QUANTITY.

Literal set: ``{"date": "1994-01-01", "discount": 0.06, "quantity": 24}``,
the spec's substitution parameters (2.4.6.3): DATE the first of January
of a year in 1993..1997, DISCOUNT in 0.02..0.09, QUANTITY 24 or 25.
"""

from __future__ import annotations

import datetime

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ORDERED = True


def _params(lits: dict):
    """Ship-date bounds [lo, hi), discount bounds [lo, hi] and quantity."""
    lo = datetime.date.fromisoformat(lits["date"])
    hi = lo.replace(year=lo.year + 1)
    d = lits["discount"]
    return lo, hi, round(d - 0.01, 2), round(d + 0.01, 2), lits["quantity"]


def build(session, lake, spec: dict, lits: dict):
    from hyperspace_tpu.plan.expr import col, sum_

    lo, hi, dlo, dhi, qty = _params(lits)
    li = session.read.parquet(lake.dirs["lineitem"])
    return (li.filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi)
                      & col("l_discount").between(dlo, dhi)
                      & (col("l_quantity") < qty))
            .agg(sum_(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def reference(lake, spec: dict, lits: dict, dtype=np.float64) -> pa.Table:
    """The same query in numpy on the host, from the parquet files.
    Revenue is computed and summed in ``dtype``."""
    lo, hi, dlo, dhi, qty = _params(lits)
    li = pq.read_table(lake.dirs["lineitem"],
                       columns=["l_extendedprice", "l_discount"],
                       filters=[("l_shipdate", ">=", lo),
                                ("l_shipdate", "<", hi),
                                ("l_discount", ">=", dlo),
                                ("l_discount", "<=", dhi),
                                ("l_quantity", "<", qty)])
    price = li.column("l_extendedprice").to_numpy().astype(dtype)
    disc = li.column("l_discount").to_numpy().astype(dtype)
    revenue = np.sum(price * disc, dtype=dtype)
    return pa.table({"revenue": pa.array([float(revenue)], pa.float64())})
