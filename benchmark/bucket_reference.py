"""The plain reference of a covering-index build, in numpy and pyarrow: the
bucket each source row belongs in, a digest of an index's bucket files,
and the row-for-row comparison of those files with the source. It imports
nothing of the program.

The bucket is the engine's documented bucket hash, transcribed
(``ops/index_build.bucket_ids_for``): each indexed value is folded to 32
bits (a 64-bit value's bits as ``lo ^ hi * 0x9E3779B9``, a 32-bit value's
bits as they are), avalanched by murmur3's 32-bit finalizer, the columns'
hashes combined boost-style in the order they are indexed, and the
combined hash taken modulo the number of buckets.

An index holds one parquet file per non-empty bucket, named
``part-<bucket, five digits>.parquet`` (Spark's bucket-file suffix), each
sorted by the indexed columns.
"""

from __future__ import annotations

import re
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np
import pyarrow as pa

GOLDEN32 = np.uint32(0x9E3779B9)
BUCKET_FILE = re.compile(r"part-(\d{5})")
COMPARE_PARTS = 8

# (rows, 64-bit sum of the row hashes, sorted by the indexed columns)
Digest = Dict[int, Tuple[int, int, bool]]


def bits64(col) -> np.ndarray:
    """A column's values as uint64 bit patterns: integers and dates
    sign-extended, float64 as its IEEE bits."""
    if pa.types.is_date32(col.type):
        col = col.cast(pa.int32())
    a = np.asarray(col.to_numpy())
    if a.dtype == np.float64:
        return a.view(np.uint64)
    return a.astype(np.int64).view(np.uint64)


def _fold32(col) -> np.ndarray:
    t = col.type
    if pa.types.is_int64(t) or pa.types.is_float64(t):
        u = bits64(col)
        lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (u >> np.uint64(32)).astype(np.uint32)
        return lo ^ (hi * GOLDEN32)
    if pa.types.is_int32(t) or pa.types.is_date32(t):
        return bits64(col).astype(np.uint32)
    raise TypeError(f"no reference hash for a {t} column")


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def bucket_ids(table: pa.Table, indexed: Sequence[str],
               num_buckets: int) -> np.ndarray:
    """The bucket of every row of ``table``."""
    h = None
    for name in indexed:
        c = _fmix32(_fold32(table.column(name)))
        h = c if h is None else \
            h ^ (c + GOLDEN32 + (h << np.uint32(6)) + (h >> np.uint32(2)))
    return (h % np.uint32(num_buckets)).astype(np.int64)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer, in place."""
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


# Odd 64-bit multipliers, one per column of a row.
ROW_WEIGHTS = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
               0xD6E8FEB86659FD93, 0xFF51AFD7ED558CCD, 0xC4CEB9FE1A85EC53)


def row_hash(table: pa.Table, cols: Sequence[str]) -> np.ndarray:
    """A 64-bit hash of each row's values in ``cols``: each value's bits
    mixed and weighted by its column, summed, and mixed again."""
    h = np.zeros(table.num_rows, np.uint64)
    for name, w in zip(cols, ROW_WEIGHTS):
        v = bits64(table.column(name)).copy()
        v = _mix64(v)
        v *= np.uint64(w)
        h += v
    return _mix64(h)


def is_sorted(table: pa.Table, cols: Sequence[str]) -> bool:
    """Whether the rows are in ascending order of ``cols``, the first
    column first."""
    if table.num_rows < 2:
        return True
    ahead = np.zeros(table.num_rows - 1, dtype=bool)
    tied = np.ones(table.num_rows - 1, dtype=bool)
    for name in cols:
        a = table.column(name)
        a = np.asarray((a.cast(pa.int32()) if pa.types.is_date32(a.type)
                        else a).to_numpy())
        ahead |= tied & (a[1:] > a[:-1])
        tied &= a[1:] == a[:-1]
    return bool(np.all(ahead | tied))


def bucket_of_file(path: str) -> int:
    """The bucket a file's name gives, or -1 where it gives none."""
    m = BUCKET_FILE.search(path.rsplit("/", 1)[-1])
    return int(m.group(1)) if m else -1


def digest(parts: List[Tuple[int, pa.Table]], indexed: Sequence[str],
           cols: Sequence[str]) -> Digest:
    """Per bucket of an index's files (``(bucket, rows)`` each): the row
    count, the sum of the row hashes modulo 2**64 (the same in any row
    order), and whether every file of the bucket is sorted."""
    out: Digest = {}
    for b, t in parts:
        n, s, ok = out.get(b, (0, 0, True))
        out[b] = (n + t.num_rows,
                  (s + int(row_hash(t, cols).sum(dtype=np.uint64)))
                  % 2**64, ok and is_sorted(t, indexed))
    return out


def reference_digest(source: pa.Table, buckets: np.ndarray,
                     cols: Sequence[str]) -> Digest:
    """The digest of the index the source's rows make: each row in its
    bucket, every bucket sorted."""
    counts = np.bincount(buckets)
    sums = np.zeros(len(counts), np.uint64)
    np.add.at(sums, buckets, row_hash(source, cols))
    return {int(b): (int(counts[b]), int(sums[b]), True)
            for b in np.flatnonzero(counts)}


def _unmatched(t: pa.Table) -> int:
    keys = [c for c in t.column_names if c != "side"]
    g = t.group_by(keys, use_threads=False).aggregate(
        [("side", "sum"), ("side", "count")])
    n_got = g.column("side_sum").to_numpy()
    n_all = g.column("side_count").to_numpy()
    return int(np.abs(n_all - 2 * n_got).sum())


def rows_wrong(got: pa.Table, want: pa.Table, cols: Sequence[str]) -> int:
    """Rows missing from ``got`` or extra in it, against ``want``, as
    multisets of the values in ``cols``. Equal rows have equal first
    values, so the rows are split by a hash of their first value into
    ``COMPARE_PARTS`` parts, compared in as many threads."""
    sides = (want, got)
    bits = {f"k{i}": np.concatenate([bits64(t.column(c)) for t in sides])
            for i, c in enumerate(cols)}
    bits["side"] = np.repeat(np.array([0, 1], np.int64),
                             [t.num_rows for t in sides])
    part = (_mix64(bits["k0"].copy()) % np.uint64(COMPARE_PARTS)).astype(
        np.uint8)
    order = np.argsort(part, kind="stable")
    ends = np.cumsum(np.bincount(part, minlength=COMPARE_PARTS))
    rows = {k: v[order] for k, v in bits.items()}
    tables = [pa.table({k: v[a:b] for k, v in rows.items()})
              for a, b in zip(np.r_[0, ends[:-1]], ends)]
    with ThreadPoolExecutor(COMPARE_PARTS) as pool:
        return sum(pool.map(_unmatched, tables))


def compare(parts: List[Tuple[int, pa.Table]], source: pa.Table,
            indexed: Sequence[str], cols: Sequence[str],
            num_buckets: int) -> Dict[str, int]:
    """An index's files row for row against the source: rows missing or
    extra, rows in a file of another bucket than their own, and files not
    sorted by the indexed columns."""
    got = pa.concat_tables([t for _, t in parts])
    in_file = np.repeat([b for b, _ in parts],
                        [t.num_rows for _, t in parts]).astype(np.int64)
    return {
        "rows_wrong": rows_wrong(got, source, cols),
        "rows_misplaced": int(np.count_nonzero(
            bucket_ids(got, indexed, num_buckets) != in_file)),
        "unsorted_buckets": sum(not is_sorted(t, indexed)
                                for _, t in parts),
    }
