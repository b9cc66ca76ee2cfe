"""The generator: deterministic per seed, and in the spec's shapes."""

import datetime

import numpy as np
import pyarrow.parquet as pq

import lake as L


def test_same_seed_same_tables_other_seed_other_tables():
    a = L.generate(0.002, 2**31 + 5)
    b = L.generate(0.002, 2**31 + 5)
    c = L.generate(0.002, 2**31 + 6)
    for name in a:
        assert a[name].equals(b[name])
    assert not a["lineitem"].equals(c["lineitem"])


def test_spec_shapes():
    t = L.generate(0.002, 7)
    li, od = t["lineitem"], t["orders"]
    assert set(t) == {"lineitem", "orders"} and od.num_rows == 3000
    assert len(li.column_names) == 16 and len(od.column_names) == 9
    okey = od.column("o_orderkey").to_numpy()
    assert np.all((okey - 1) % 32 < 8)
    lkey = li.column("l_orderkey").to_numpy()
    per_order = np.unique(lkey, return_counts=True)[1]
    assert per_order.min() >= 1 and per_order.max() <= 7
    assert set(np.unique(lkey)) == set(okey)
    assert np.all(od.column("o_custkey").to_numpy() % 3 != 0)
    odate = dict(zip(okey, od.column("o_orderdate").to_pylist()))
    ship = li.column("l_shipdate").to_pylist()
    lag = np.array([(s - odate[k]).days for s, k in zip(ship, lkey)])
    assert lag.min() >= 1 and lag.max() <= 121
    end = L.END_DATE - datetime.timedelta(days=151)
    assert max(odate.values()) <= end
    price = li.column("l_extendedprice").to_numpy()
    qty = li.column("l_quantity").to_numpy()
    part = li.column("l_partkey").to_numpy()
    assert np.allclose(price, qty * L.retail_price(part))
    comment = li.column("l_comment").to_pylist()
    assert all(10 <= len(c) <= 43 for c in comment)


def test_lineitem_alone_is_the_same_lineitem():
    both = L.generate(0.002, 2**31 + 5)
    alone = L.generate(0.002, 2**31 + 5, ("lineitem",))
    assert set(alone) == {"lineitem"}
    assert alone["lineitem"].equals(both["lineitem"])


def test_layout_sorted_by_ship_date(tmp_path):
    config = {"scale_factor": 0.002, "tables": {
        "lineitem": {"files": 5, "sort_by": "l_shipdate"},
        "orders": {"files": 3}}}
    lake = L.write_lake(str(tmp_path), config, 11)
    assert lake.files == {"lineitem": 5, "orders": 3}
    sizes, spans = [], []
    for f in sorted((tmp_path / "lineitem").iterdir()):
        d = pq.read_table(f).column("l_shipdate").cast("int32").to_numpy()
        assert np.all(np.diff(d) >= 0)
        sizes.append(len(d))
        spans.append((d[0], d[-1]))
    assert sum(sizes) == lake.rows["lineitem"]
    assert len(set(sizes[:-1])) == 1 and sizes[-1] <= sizes[0]
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
