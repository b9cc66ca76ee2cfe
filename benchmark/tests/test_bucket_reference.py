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


def _faulty(table, fault):
    """``table`` with one kind of fault, its rows shuffled."""
    f64 = table.column("f64").to_numpy().copy()
    if fault == "row_dropped":
        table = table.slice(1)
    elif fault == "rows_repeated":
        table = pa.concat_tables([table, table.slice(0, 10)])
    elif fault == "value_off_by_an_ulp":
        f64[3] = np.nextafter(f64[3], np.inf)
        table = table.set_column(1, "f64", pa.array(f64))
    elif fault == "values_swapped_under_one_key":
        i, j = np.flatnonzero(table.column("i64").to_numpy() ==
                              table.column("i64")[0].as_py())[:2]
        f64[[i, j]] = f64[[j, i]]
        table = table.set_column(1, "f64", pa.array(f64))
    return table.take(np.random.default_rng(3).permutation(table.num_rows))


@pytest.mark.parametrize("fault", ["none", "row_dropped", "rows_repeated",
                                   "value_off_by_an_ulp",
                                   "values_swapped_under_one_key"])
def test_rows_wrong_is_the_multiset_difference(table, fault):
    """Rows that share their first value, the one the comparison splits
    its work by, and repeated rows, counted as a plain multiset count
    does."""
    from collections import Counter

    cols = table.column_names
    keyed = table.set_column(0, "i64", pa.array(
        table.column("i64").to_numpy() % 50))
    got = _faulty(keyed, fault)

    def rows(t):
        return Counter(zip(*(R.bits64(t.column(c)).tolist() for c in cols)))

    g, w = rows(got), rows(keyed)
    want = sum(((g - w) + (w - g)).values())
    assert R.rows_wrong(got, keyed, cols) == want
    assert (want == 0) == (fault == "none")
