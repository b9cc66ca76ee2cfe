"""The comparison that decides ``correct``: an answer against the plain
reference's answer to the same query.

Two numbers per answer:

- ``rows_wrong``: rows that are missing, extra, or differ in a column that
  is not floating point. Exact; its limit is 0.
- ``rel_err``: the largest relative error of a floating-point value, over
  rows that agree otherwise. Its limit sits between what the engine reads
  and what a reference computed one precision lower reads.
"""

from __future__ import annotations

from collections import Counter

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def cast_floats(t: pa.Table, dtype) -> pa.Table:
    """``t`` with each floating-point column rounded through ``dtype``."""
    if dtype == np.float64:
        return t
    cols = []
    for c in t.columns:
        if pa.types.is_floating(c.type):
            c = pa.array(c.to_numpy().astype(dtype).astype(np.float64))
        cols.append(c)
    return pa.table(cols, names=t.column_names)


def _split(t: pa.Table):
    floats = [c for c in t.column_names
              if pa.types.is_floating(t.schema.field(c).type)]
    exact = [c for c in t.column_names if c not in floats]
    return exact, floats


def compare(got: pa.Table, want: pa.Table, ordered: bool):
    """(rows_wrong, rel_err) of ``got`` against ``want``."""
    if got.column_names != want.column_names:
        return max(got.num_rows, want.num_rows, 1), float("inf")
    exact, floats = _split(want)
    if not ordered and want.num_rows:
        keys = [(c, "ascending") for c in exact + floats]
        got, want = got.sort_by(keys), want.sort_by(keys)
    if got.num_rows != want.num_rows:
        g = Counter(zip(*[got.column(c).to_pylist() for c in exact]))
        w = Counter(zip(*[want.column(c).to_pylist() for c in exact]))
        return sum(((g - w) + (w - g)).values()), float("inf")
    n = want.num_rows
    same = np.ones(n, dtype=bool)
    for c in exact:
        a, b = got.column(c), want.column(c)
        if a.type != b.type:
            return n, float("inf")
        same &= np.asarray(
            pc.equal(a, b).fill_null(False).to_numpy(
                zero_copy_only=False), dtype=bool)
    rel = 0.0
    for c in floats:
        a = got.column(c).to_numpy(zero_copy_only=False)[same]
        b = want.column(c).to_numpy(zero_copy_only=False)[same]
        if len(b):
            err = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
            rel = max(rel, float(np.nan_to_num(err, nan=np.inf).max()))
    return int(n - same.sum()), rel
