"""Data-skipping index tests: sketch build, scan pruning, refresh lifecycle.

The disable-and-compare oracle applies throughout: pruned-scan results must
equal full-scan results (sketches may only remove files that cannot match).
"""

import datetime
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.api import (BloomFilterSketch, DataSkippingIndexConfig,
                                Hyperspace, IndexConfig, MinMaxSketch)
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.index.constants import IndexConstants, States
from hyperspace_tpu.ops import sketches as sk
from hyperspace_tpu.plan.expr import col
from hyperspace_tpu.plan.nodes import Scan


def write_partitioned(root, name, df, key, parts):
    """Write one file per contiguous key range so min/max sketches have
    non-overlapping ranges to prune on."""
    d = root / name
    d.mkdir(parents=True, exist_ok=True)
    df = df.sort_values(key).reset_index(drop=True)
    step = (len(df) + parts - 1) // parts
    for i in range(parts):
        chunk = df.iloc[i * step:(i + 1) * step]
        if len(chunk):
            pq.write_table(pa.Table.from_pandas(chunk.reset_index(drop=True)),
                           d / f"part{i}.parquet")
    return str(d)


@pytest.fixture()
def env(tmp_path):
    rng = np.random.default_rng(3)
    n = 2000
    df = pd.DataFrame({
        "k": np.arange(n, dtype=np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
        "d": [datetime.date(1995, 1, 1) + datetime.timedelta(days=int(x) % 300)
              for x in np.arange(n)],
        "s": [f"cat{int(x) % 7}" for x in np.arange(n)],
    })
    path = write_partitioned(tmp_path, "data", df, "k", 8)
    session = hst.Session(system_path=str(tmp_path / "indexes"))
    return dict(session=session, hs=Hyperspace(session), path=path,
                df=df, tmp=tmp_path)


def scan_files(plan):
    (leaf,) = [l for l in plan.collect_leaves() if isinstance(l, Scan)]
    return leaf.relation.all_files()


def check_disable_and_compare(session, df):
    session.enable_hyperspace()
    with_idx = df.to_pandas()
    session.disable_hyperspace()
    without = df.to_pandas()
    session.enable_hyperspace()
    a = with_idx.sort_values(list(with_idx.columns)).reset_index(drop=True)
    b = without.sort_values(list(without.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b, check_dtype=False)
    return with_idx


class TestSketchPrimitives:
    def test_bloom_roundtrip_int(self):
        import jax.numpy as jnp
        from hyperspace_tpu.execution.columnar import Column
        from hyperspace_tpu.schema import INT64
        values = np.array([3, 17, 99, 12345, -8], dtype=np.int64)
        c = Column(INT64, jnp.asarray(values))
        m, k = sk.bloom_parameters(64, 0.01)
        bits = sk.bloom_build(c, m, k)
        for v in values:
            assert sk.bloom_might_contain(bits, int(v), INT64, m, k)
        misses = sum(sk.bloom_might_contain(bits, int(v), INT64, m, k)
                     for v in range(1000, 1200))
        assert misses <= 10  # fpp well under control.

    def test_bloom_roundtrip_string(self):
        import jax.numpy as jnp
        from hyperspace_tpu.execution.columnar import Column
        from hyperspace_tpu.schema import STRING
        words = np.array(["alpha", "beta", "gamma"])
        c = Column(STRING, jnp.asarray(np.array([0, 1, 2], np.int32)),
                   None, words)
        m, k = sk.bloom_parameters(16, 0.01)
        bits = sk.bloom_build(c, m, k)
        for w in words:
            assert sk.bloom_might_contain(bits, w, STRING, m, k)
        assert not sk.bloom_might_contain(bits, "delta", STRING, m, k)

    def test_minmax_with_nulls(self):
        import jax.numpy as jnp
        from hyperspace_tpu.execution.columnar import Column
        from hyperspace_tpu.schema import INT64
        c = Column(INT64, jnp.asarray(np.array([5, 1, 9], np.int64)),
                   jnp.asarray(np.array([True, False, True])))
        assert sk.minmax_values(c) == (5, 9)
        c_all_null = Column(INT64, jnp.asarray(np.array([5], np.int64)),
                            jnp.asarray(np.array([False])))
        assert sk.minmax_values(c_all_null) == (None, None)


class TestDataSkippingE2E:
    def test_minmax_prunes_range_scan(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsMinMax", [MinMaxSketch("k")]))
        entry = hs.index_manager.get_index("dsMinMax")
        assert entry.state == States.ACTIVE
        assert entry.derivedDataset.kind == "DataSkippingIndex"

        session.enable_hyperspace()
        q = df.filter(col("k") < 250).select("k", "v")
        plan = q.optimized_plan()
        kept = scan_files(plan)
        assert len(kept) == 1  # 8 range-partitioned files, k<250 hits one.
        out = check_disable_and_compare(session, q)
        assert len(out) == 250

    def test_same_index_name_in_two_lakes_prunes_each(self, tmp_path):
        """Sketch tables are cached by the index's path: a second lake
        whose skipping index has the same name (and log id) prunes by its
        own sketches, not the first lake's."""
        for lake, base in (("a", 0), ("b", 5000)):
            df = pd.DataFrame({"k": np.arange(base, base + 800,
                                              dtype=np.int64),
                               "v": np.arange(800, dtype=np.int64)})
            path = write_partitioned(tmp_path / lake, "data", df, "k", 8)
            session = hst.Session(
                system_path=str(tmp_path / lake / "indexes"))
            src = session.read.parquet(path)
            Hyperspace(session).create_index(src, DataSkippingIndexConfig(
                "dsShared", [MinMaxSketch("k")]))
            session.enable_hyperspace()
            q = src.filter(col("k") < base + 100).select("k", "v")
            assert len(scan_files(q.optimized_plan())) == 1
            out = check_disable_and_compare(session, q)
            assert len(out) == 100

    def test_bloom_prunes_equality(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsBloom", [BloomFilterSketch("k", fpp=0.001, expected_items=300)]))
        session.enable_hyperspace()
        q = df.filter(col("k") == 777).select("k", "v")
        kept = scan_files(q.optimized_plan())
        assert len(kept) < 8
        out = check_disable_and_compare(session, q)
        assert len(out) == 1

    def test_string_equality_prunes(self, env, tmp_path):
        session, hs = env["session"], env["hs"]
        # Files partitioned by string category.
        df = env["df"]
        d = tmp_path / "bycat"
        d.mkdir()
        for i, (cat, chunk) in enumerate(df.groupby("s")):
            pq.write_table(pa.Table.from_pandas(chunk.reset_index(drop=True)),
                           d / f"part{i}.parquet")
        data = session.read.parquet(str(d))
        hs.create_index(data, DataSkippingIndexConfig(
            "dsStr", [MinMaxSketch("s"), BloomFilterSketch("s")]))
        session.enable_hyperspace()
        q = data.filter(col("s") == "cat3").select("k", "s")
        kept = scan_files(q.optimized_plan())
        assert len(kept) == 1
        out = check_disable_and_compare(session, q)
        assert len(out) == (df.s == "cat3").sum()

    def test_date_range_prunes(self, env, tmp_path):
        session, hs = env["session"], env["hs"]
        df = env["df"]
        d = write_partitioned(tmp_path, "bydate", df, "d", 6)
        data = session.read.parquet(d)
        hs.create_index(data, DataSkippingIndexConfig(
            "dsDate", [MinMaxSketch("d")]))
        session.enable_hyperspace()
        cutoff = datetime.date(1995, 2, 1)
        q = data.filter(col("d") < cutoff).select("k", "d")
        kept = scan_files(q.optimized_plan())
        assert 0 < len(kept) < 6
        out = check_disable_and_compare(session, q)
        assert len(out) == (df.d < cutoff).sum()

    def test_disjunction_prunes_union(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsOr", [MinMaxSketch("k")]))
        session.enable_hyperspace()
        q = df.filter((col("k") < 100) | (col("k") > 1900)).select("k")
        kept = scan_files(q.optimized_plan())
        assert len(kept) == 2
        out = check_disable_and_compare(session, q)
        assert len(out) == 100 + 99

    def test_in_list_prunes(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsIn", [MinMaxSketch("k")]))
        session.enable_hyperspace()
        q = df.filter(col("k").isin([5, 6, 1999])).select("k", "v")
        kept = scan_files(q.optimized_plan())
        assert len(kept) == 2
        out = check_disable_and_compare(session, q)
        assert len(out) == 3

    def test_unprunable_predicate_keeps_scan(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsNo", [MinMaxSketch("k")]))
        session.enable_hyperspace()
        # Predicate on a non-sketched column: plan unchanged (8 files).
        q = df.filter(col("v") < 100).select("k", "v")
        assert len(scan_files(q.optimized_plan())) == 8

    def test_prune_to_empty(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsEmpty", [MinMaxSketch("k")]))
        session.enable_hyperspace()
        q = df.filter(col("k") > 10_000).select("k", "v")
        kept = scan_files(q.optimized_plan())
        assert kept == []
        out = q.to_pandas()
        assert len(out) == 0

    def test_covering_index_wins_over_skipping(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsBoth", [MinMaxSketch("k")]))
        hs.create_index(df, IndexConfig("ciBoth", ["k"], ["v"]))
        session.enable_hyperspace()
        q = df.filter(col("k") < 250).select("k", "v")
        from hyperspace_tpu.plan.nodes import IndexScan
        leaves = q.optimized_plan().collect_leaves()
        assert any(isinstance(l, IndexScan) and l.index_entry.name == "ciBoth"
                   for l in leaves)

    def test_stale_signature_not_applied(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsStale", [MinMaxSketch("k")]))
        extra = env["df"].iloc[:5].copy()
        extra["k"] += 50_000
        pq.write_table(pa.Table.from_pandas(extra.reset_index(drop=True)),
                       env["tmp"] / "data" / "late.parquet")
        session.enable_hyperspace()
        fresh = session.read.parquet(env["path"])
        q = fresh.filter(col("k") < 250).select("k", "v")
        assert len(scan_files(q.optimized_plan())) == 9  # unpruned.
        check_disable_and_compare(session, q)


class TestDataSkippingRefresh:
    def test_full_refresh(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsRef", [MinMaxSketch("k")]))
        extra = env["df"].iloc[:50].copy()
        extra["k"] += 50_000
        pq.write_table(pa.Table.from_pandas(extra.reset_index(drop=True)),
                       env["tmp"] / "data" / "x.parquet")
        hs.refresh_index("dsRef", "full")
        entry = hs.index_manager.get_index("dsRef")
        assert entry.log_version == 1

        session.enable_hyperspace()
        fresh = session.read.parquet(env["path"])
        q = fresh.filter(col("k") > 49_000).select("k")
        kept = scan_files(q.optimized_plan())
        assert len(kept) == 1
        out = check_disable_and_compare(session, q)
        assert len(out) == 50

    def test_incremental_refresh_with_delete(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsInc", [MinMaxSketch("k"), BloomFilterSketch("k")]))
        os.remove(os.path.join(env["path"], "part0.parquet"))
        extra = env["df"].iloc[:50].copy()
        extra["k"] += 50_000
        pq.write_table(pa.Table.from_pandas(extra.reset_index(drop=True)),
                       env["tmp"] / "data" / "x.parquet")
        hs.refresh_index("dsInc", "incremental")  # no lineage needed.

        session.enable_hyperspace()
        fresh = session.read.parquet(env["path"])
        q = fresh.filter(col("k") == 50_010).select("k", "v")
        kept = scan_files(q.optimized_plan())
        assert len(kept) == 1
        out = check_disable_and_compare(session, q)
        assert len(out) == 1

    def test_quick_refresh_unsupported(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsQ", [MinMaxSketch("k")]))
        pq.write_table(pa.Table.from_pandas(env["df"].iloc[:5]),
                       env["tmp"] / "data" / "y.parquet")
        with pytest.raises(HyperspaceException, match="not supported"):
            hs.refresh_index("dsQ", "quick")

    def test_lifecycle_delete_vacuum(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsLc", [MinMaxSketch("k")]))
        hs.delete_index("dsLc")
        assert hs.index_manager.get_index("dsLc").state == States.DELETED
        session.enable_hyperspace()
        q = df.filter(col("k") < 250).select("k")
        assert len(scan_files(q.optimized_plan())) == 8  # not applied.
        hs.vacuum_index("dsLc")
        assert hs.index_manager.get_index("dsLc").state == States.DOESNOTEXIST

    def test_listing_includes_skipping_index(self, env):
        session, hs = env["session"], env["hs"]
        df = session.read.parquet(env["path"])
        hs.create_index(df, DataSkippingIndexConfig(
            "dsList", [MinMaxSketch("k"), BloomFilterSketch("v")]))
        listing = hs.indexes()
        assert "dsList" in list(listing["name"])


class TestValueListSketch:
    """Exact distinct-values sketch: equality/IN pruning with no false
    positives; over-cardinality files store no list and are always kept."""

    def _build(self, tmp_path, session, regions_per_file):
        """One file per region set; a 'cat' column holds those regions."""
        d = tmp_path / "vl"
        d.mkdir()
        rng = np.random.default_rng(4)
        for i, regions in enumerate(regions_per_file):
            n = 500
            t = pa.table({
                "cat": pa.array(rng.choice(regions, n)),
                "v": pa.array(rng.integers(0, 100, n).astype(np.int64)),
            })
            pq.write_table(t, d / f"part{i}.parquet")
        return str(d)

    def test_equality_prunes_exactly(self, tmp_path):
        from hyperspace_tpu.api import ValueListSketch
        session = hst.Session(system_path=str(tmp_path / "idx"))
        hs = Hyperspace(session)
        path = self._build(tmp_path, session,
                           [["ca", "wa"], ["ny", "nj"], ["tx"], ["ca", "tx"]])
        t = session.read.parquet(path)
        hs.create_index(t, DataSkippingIndexConfig(
            "vl_idx", [ValueListSketch("cat")]))
        session.enable_hyperspace()
        q = t.filter(col("cat") == "tx")
        leaves = q.optimized_plan().collect_leaves()
        kept = leaves[0].relation.all_files()
        assert len(kept) == 2  # files 2 and 3 only
        got = q.to_pandas()
        session.disable_hyperspace()
        raw = q.to_pandas()
        assert len(got) == len(raw)

    def test_in_list_unions_memberships(self, tmp_path):
        from hyperspace_tpu.api import ValueListSketch
        session = hst.Session(system_path=str(tmp_path / "idx"))
        hs = Hyperspace(session)
        path = self._build(tmp_path, session,
                           [["ca"], ["ny"], ["tx"], ["wa"]])
        t = session.read.parquet(path)
        hs.create_index(t, DataSkippingIndexConfig(
            "vl_in", [ValueListSketch("cat")]))
        session.enable_hyperspace()
        q = t.filter(col("cat").isin(["ca", "wa"]))
        kept = q.optimized_plan().collect_leaves()[0].relation.all_files()
        assert len(kept) == 2
        assert len(q.to_pandas()) == 1000  # both files fully match

    def test_over_cardinality_file_never_pruned(self, tmp_path):
        from hyperspace_tpu.api import ValueListSketch
        session = hst.Session(system_path=str(tmp_path / "idx"))
        hs = Hyperspace(session)
        d = tmp_path / "big"
        d.mkdir()
        rng = np.random.default_rng(6)
        # File 0: 3000 distinct ints (over max_values=64) → no list stored.
        pq.write_table(pa.table({
            "k": pa.array(np.arange(3000, dtype=np.int64)),
        }), d / "wide.parquet")
        # File 1: only {1, 2}.
        pq.write_table(pa.table({
            "k": pa.array(rng.choice([1, 2], 500).astype(np.int64)),
        }), d / "narrow.parquet")
        t = session.read.parquet(str(d))
        hs.create_index(t, DataSkippingIndexConfig(
            "vl_oc", [ValueListSketch("k", max_values=64)]))
        session.enable_hyperspace()
        # 7 is absent from BOTH files, but only narrow can prove it.
        q = t.filter(col("k") == 7)
        kept = q.optimized_plan().collect_leaves()[0].relation.all_files()
        assert len(kept) == 1 and kept[0].endswith("wide.parquet")
        got = q.to_pandas()
        session.disable_hyperspace()
        assert len(got) == len(q.to_pandas()) == 1

    def test_int_and_date_values(self, tmp_path):
        from hyperspace_tpu.api import ValueListSketch
        session = hst.Session(system_path=str(tmp_path / "idx"))
        hs = Hyperspace(session)
        d = tmp_path / "dates"
        d.mkdir()
        day = lambda i: datetime.date(2024, 1, 1) + datetime.timedelta(days=i)
        for i in range(3):
            pq.write_table(pa.table({
                "d": pa.array([day(i)] * 100, pa.date32()),
                "v": pa.array(np.arange(100, dtype=np.int64)),
            }), d / f"p{i}.parquet")
        t = session.read.parquet(str(d))
        hs.create_index(t, DataSkippingIndexConfig(
            "vl_d", [ValueListSketch("d")]))
        session.enable_hyperspace()
        q = t.filter(col("d") == day(1))
        kept = q.optimized_plan().collect_leaves()[0].relation.all_files()
        assert len(kept) == 1
        assert len(q.to_pandas()) == 100
