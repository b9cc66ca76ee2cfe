"""The plain reference of an index build against the engine's documented
bucket hash, and its digest: the same bucket for every value of each type
an index can be keyed on, and a digest that ignores row order and sees a
moved, dropped or misordered row."""

import numpy as np
import pyarrow as pa
import pytest

import bucket_reference as R

N = 5000


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(7)
    return pa.table({
        "i64": rng.integers(-2**62, 2**62, N),
        "f64": rng.normal(size=N) * 1e6,
        "i32": rng.integers(-2**31, 2**31, N).astype(np.int32),
        "date": pa.array(rng.integers(0, 20000, N).astype(np.int32))
        .cast(pa.date32()),
    })


@pytest.mark.parametrize("cols", [["i64"], ["f64"], ["i32"], ["date"],
                                  ["i64", "date", "f64"]])
def test_buckets_are_the_engines(table, cols):
    import jax.numpy as jnp
    from hyperspace_tpu.ops import kernels

    dtypes = {"i64": "int64", "f64": "float64", "i32": "int32",
              "date": "date"}
    h = None
    for c in cols:
        col = table.column(c)
        data = (col.cast(pa.int32()) if c == "date" else col).to_numpy()
        ch = kernels.hash32_values(jnp.asarray(data), dtypes[c])
        h = ch if h is None else kernels.hash_combine(h, ch)
    want = np.asarray(kernels.bucket_ids(h, 200))
    np.testing.assert_array_equal(R.bucket_ids(table, cols, 200), want)


def split(table, buckets):
    """The table as an index would hold it: a file per bucket, sorted."""
    return [(int(b), table.filter(pa.array(buckets == b)).sort_by("i64"))
            for b in np.unique(buckets)]


def test_digest_is_the_reference_digest_in_any_row_order(table):
    cols = table.column_names
    buckets = R.bucket_ids(table, ["i64"], 16)
    want = R.reference_digest(table, buckets, cols)
    assert R.digest(split(table, buckets), ["i64"], cols) == want
    shuffled = table.take(np.random.default_rng(1).permutation(N))
    assert R.reference_digest(shuffled, R.bucket_ids(shuffled, ["i64"], 16),
                              cols) == want


def test_digest_and_compare_see_each_fault(table):
    cols = table.column_names
    buckets = R.bucket_ids(table, ["i64"], 16)
    want = R.reference_digest(table, buckets, cols)
    parts = split(table, buckets)
    assert R.compare(parts, table, ["i64"], cols, 16) == {
        "rows_wrong": 0, "rows_misplaced": 0, "unsorted_buckets": 0}

    (b0, t0), (b1, t1) = parts[:2]
    dropped = [(b0, t0.slice(1))] + parts[1:]
    moved = [(b0, t0.slice(1)),
             (b1, pa.concat_tables([t1, t0.slice(0, 1)]).sort_by("i64"))] \
        + parts[2:]
    n = t0.num_rows
    swapped = [(b0, t0.take([n - 1] + list(range(1, n - 1)) + [0]))] \
        + parts[1:]
    for faulty, check in ((dropped, "rows_wrong"), (moved, "rows_misplaced"),
                          (swapped, "unsorted_buckets")):
        assert R.digest(faulty, ["i64"], cols) != want
        got = R.compare(faulty, table, ["i64"], cols, 16)
        assert got.pop(check) == 1, check
        assert not any(got.values()), check
