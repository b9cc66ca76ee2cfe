"""SPMD query execution (execution/spmd.py) over the 8-device CPU mesh.

The product query path for multi-chip: aggregation subtrees run SPMD with
XLA collectives; everything here asserts (a) the SPMD path is actually
taken (DISPATCH_COUNT advances), and (b) results equal the single-device
executor (disable-and-compare through the same public DataFrame API) or a
pandas oracle where the single-device path lacks the capability.
"""

import os

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.execution import spmd
from hyperspace_tpu.index.constants import IndexConstants
from hyperspace_tpu.plan.expr import avg, col, count, max_, min_, sum_
from hyperspace_tpu.telemetry import span_names as sn
from hyperspace_tpu.telemetry.constants import TelemetryConstants as TC


@pytest.fixture()
def session(tmp_system_path):
    s = hst.Session(system_path=tmp_system_path)
    # Gate off: these fixtures are deliberately small meshes.
    s.conf.set(IndexConstants.TPU_DISTRIBUTED_MIN_STREAM_ROWS, "0")
    return s


@pytest.fixture()
def lineitem_dir(tmp_path):
    rng = np.random.default_rng(11)
    n = 6000
    t = pa.table({
        "l_orderkey": rng.integers(0, 500, n).astype(np.int64),
        "l_partkey": rng.integers(0, 80, n).astype(np.int64),
        "l_qty": rng.integers(1, 50, n).astype(np.int64),
        "l_price": np.round(rng.uniform(100, 1000, n), 2),
        "l_tag": rng.choice(["a", "b", "c", "d"], n),
    })
    d = tmp_path / "lineitem"
    d.mkdir()
    pq.write_table(t, str(d / "part0.parquet"))
    return str(d)


@pytest.fixture()
def orders_dir(tmp_path):
    rng = np.random.default_rng(12)
    n = 500
    t = pa.table({
        "o_orderkey": np.arange(n, dtype=np.int64),
        "o_pri": rng.integers(0, 4, n).astype(np.int64),
        "o_flag": rng.choice(["X", "Y"], n),
    })
    d = tmp_path / "orders"
    d.mkdir()
    pq.write_table(t, str(d / "part0.parquet"))
    return str(d)


def run_both(session, make_query):
    """Run the query with SPMD enabled (asserting dispatch) and disabled;
    return both arrow tables."""
    before = spmd.DISPATCH_COUNT
    dist = make_query().to_arrow()
    assert spmd.DISPATCH_COUNT > before, "SPMD path was not taken"
    session.conf.set(IndexConstants.TPU_DISTRIBUTED_ENABLED, "false")
    try:
        single = make_query().to_arrow()
    finally:
        session.conf.set(IndexConstants.TPU_DISTRIBUTED_ENABLED, "true")
    return dist, single


def assert_tables_equal(a, b, float_cols=()):
    pa_, pb = a.to_pydict(), b.to_pydict()
    assert list(pa_.keys()) == list(pb.keys())
    for k in pa_:
        if k in float_cols:
            assert np.allclose(pa_[k], pb[k], equal_nan=True), k
        else:
            assert pa_[k] == pb[k], k


class TestGlobalAggregate:
    def test_filter_sum_count(self, session, lineitem_dir):
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(session, lambda: li.filter(col("l_qty") > 25).agg(
            sum_(col("l_price")).alias("sp"), count(None).alias("n")))
        assert_tables_equal(d, s, float_cols=("sp",))

    def test_min_max_avg(self, session, lineitem_dir):
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(session, lambda: li.filter(col("l_tag") != "d").agg(
            min_(col("l_price")).alias("mn"), max_(col("l_qty")).alias("mx"),
            avg(col("l_price")).alias("av")))
        assert_tables_equal(d, s, float_cols=("mn", "av"))

    def test_min_max_string(self, session, lineitem_dir):
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(session, lambda: li.filter(col("l_qty") < 10).agg(
            min_(col("l_tag")).alias("mn"), max_(col("l_tag")).alias("mx")))
        assert_tables_equal(d, s)

    def test_arithmetic_agg_expr(self, session, lineitem_dir):
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(session, lambda: li.agg(
            sum_(col("l_price") * col("l_qty")).alias("rev")))
        assert_tables_equal(d, s, float_cols=("rev",))


class TestGroupedAggregate:
    def test_group_by_int(self, session, lineitem_dir):
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(
            session,
            lambda: li.filter(col("l_qty") > 5).group_by("l_orderkey").agg(
                sum_(col("l_price")).alias("sp"), count(None).alias("n"),
                min_(col("l_qty")).alias("mq")))
        assert_tables_equal(d, s, float_cols=("sp",))

    def test_group_by_string(self, session, lineitem_dir):
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(session, lambda: li.group_by("l_tag").agg(
            avg(col("l_price")).alias("ap")))
        assert_tables_equal(d, s, float_cols=("ap",))

    def test_group_by_two_cols(self, session, lineitem_dir):
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(
            session,
            lambda: li.group_by("l_tag", "l_partkey").agg(
                count(None).alias("n"), max_(col("l_price")).alias("mp")))
        assert_tables_equal(d, s, float_cols=("mp",))


class TestDistributedFinalMerge:
    """The grouped two-phase shuffle: partial groups hash-route to owner
    devices and combine there, so the host receives disjoint final groups
    (spmd.py distributed-final-merge block). The virtual CPU mesh defaults
    to the host merge (cost decision, _use_routed_merge), so these tests
    force the routed path on."""

    @pytest.fixture(autouse=True)
    def _force_routed(self, monkeypatch):
        monkeypatch.setenv("HST_SPMD_ROUTED_MERGE", "on")

    def test_host_receives_disjoint_groups(self, session, lineitem_dir,
                                           monkeypatch):
        """Every (key, null-flag) group must appear on exactly one device
        after the routed merge — pinned by inspecting the program outputs
        the host merge consumes."""
        captured = {}
        orig = spmd._merge_grouped

        def spy(out, agg_specs, group_cols, col_meta, **kw):
            captured["out"] = out
            captured["group_cols"] = list(group_cols)
            return orig(out, agg_specs, group_cols, col_meta, **kw)

        monkeypatch.setattr(spmd, "_merge_grouped", spy)
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(session, lambda: li.group_by("l_orderkey").agg(
            sum_(col("l_price")).alias("sp")))
        assert_tables_equal(d, s, float_cols=("sp",))
        out = captured["out"]
        n_dev = len(jax.devices())
        gvalid = np.asarray(jax.device_get(out["gvalid"]))
        keys = np.asarray(jax.device_get(out["g:l_orderkey"]))
        per_dev = len(gvalid) // n_dev
        owner_sets = []
        for dd in range(n_dev):
            sl = slice(dd * per_dev, (dd + 1) * per_dev)
            owner_sets.append(set(keys[sl][gvalid[sl]].tolist()))
        for i in range(n_dev):
            for j in range(i + 1, n_dev):
                dup = owner_sets[i] & owner_sets[j]
                assert not dup, f"groups {dup} owned by devices {i} and {j}"

    def test_capacity_escalation_on_many_groups(self, session, tmp_path,
                                                monkeypatch):
        """With G pinned tiny, per-device partials fit but a single owner
        can exceed G2=G — escalation must recompile and still produce the
        exact answer (hard bound n_dev*G makes it terminate)."""
        monkeypatch.setattr(spmd, "MAX_LOCAL_GROUPS", 16)
        rng = np.random.default_rng(44)
        n = 128 * 31
        # ≤16 distinct keys per device shard (shards are contiguous row
        # ranges), 128 distinct overall.
        keys = np.repeat(np.arange(128, dtype=np.int64), n // 128)
        t = pa.table({"k": keys, "v": np.round(rng.uniform(0, 10, n), 3)})
        d = tmp_path / "manygroups"
        d.mkdir()
        pq.write_table(t, str(d / "p.parquet"))
        df = session.read.parquet(str(d))
        dist, single = run_both(session, lambda: df.group_by("k").agg(
            sum_(col("v")).alias("sv"), count(None).alias("n")))
        assert_tables_equal(dist, single, float_cols=("sv",))
        assert dist.num_rows == 128


class TestBroadcastJoin:
    def test_join_grouped(self, session, lineitem_dir, orders_dir):
        li = session.read.parquet(lineitem_dir)
        od = session.read.parquet(orders_dir)
        d, s = run_both(
            session,
            lambda: li.join(od, on=col("l_orderkey") == col("o_orderkey"))
            .filter(col("o_pri") < 2)
            .group_by("o_flag")
            .agg(sum_(col("l_price")).alias("sp"), count(None).alias("n")))
        assert_tables_equal(d, s, float_cols=("sp",))

    def test_join_global(self, session, lineitem_dir, orders_dir):
        li = session.read.parquet(lineitem_dir)
        od = session.read.parquet(orders_dir)
        d, s = run_both(
            session,
            lambda: li.join(od, on=col("l_orderkey") == col("o_orderkey"))
            .agg(sum_(col("o_pri")).alias("so"), count(None).alias("n")))
        assert_tables_equal(d, s)

    def test_project_redefined_join_key(self, session, lineitem_dir,
                                        orders_dir):
        # A Project below the Join that *redefines* the stream join key
        # (computed expression under the same name) must feed the join
        # prep the post-project metadata, not stale leaf metadata.
        li = session.read.parquet(lineitem_dir)
        od = session.read.parquet(orders_dir)
        d, s = run_both(
            session,
            lambda: li.select((col("l_orderkey") + 0).alias("l_orderkey"),
                              "l_price")
            .join(od, on=col("l_orderkey") == col("o_orderkey"))
            .agg(sum_(col("l_price")).alias("sp"),
                 sum_(col("o_pri")).alias("so"), count(None).alias("n")))
        assert_tables_equal(d, s, float_cols=("sp",))

    def test_many_to_many_exchange_join(self, session, lineitem_dir):
        # Self-join on a non-unique key: the broadcast m:1 requirement
        # fails, and the SPMD path now routes BOTH sides over the mesh
        # with an all-to-all and merge-joins locally (the reference's
        # shuffle join) instead of falling back.
        li = session.read.parquet(lineitem_dir)
        li2 = li.select(col("l_orderkey").alias("r_orderkey"),
                        col("l_qty").alias("r_qty"))
        before = spmd.DISPATCH_COUNT
        out = (li.join(li2, on=col("l_orderkey") == col("r_orderkey"))
               .agg(count(None).alias("n"))).to_arrow()
        assert spmd.DISPATCH_COUNT > before, "exchange join was not taken"
        # Oracle: sum of squared per-key multiplicities.
        t = pq.read_table(os.path.join(lineitem_dir, "part0.parquet"))
        counts = pd.Series(t.column("l_orderkey").to_numpy()).value_counts()
        assert out.to_pydict()["n"] == [int((counts ** 2).sum())]


def _probe_tables(session, tmp_path, kind, how):
    """Stream keys 0..119 against a broadcast side covering 10..89, so
    stream keys fall below and above the side's range, in the key kind
    ``kind``; semi/anti sides repeat every key. Returns (stream, side,
    join condition, output columns)."""
    rng = np.random.default_rng(61)
    n = 6000  # 1,024 stream rows a device: room for the smallest table
    k = rng.integers(0, 120, n)
    dk = np.arange(10, 90)
    if how in ("semi", "anti"):
        dk = np.repeat(dk, 2)
    dval = rng.integers(0, 9, len(dk)).astype(np.int64)
    v = np.arange(n, dtype=np.int64)
    on = col("k") == col("dk")
    if kind == "packed":
        fact = {"a": k % 10, "b": k // 10, "v": v}
        dim = {"ra": dk % 10, "rb": dk // 10, "dval": dval}
        on = (col("a") == col("ra")) & (col("b") == col("rb"))
    else:
        conv = {
            "int64": lambda x: x.astype(np.int64),
            "int32": lambda x: x.astype(np.int32),
            "negative": lambda x: x.astype(np.int64) - 60,
            "sparse": lambda x: x.astype(np.int64) * 1000,
            "nulls": lambda x: x.astype(np.int64),
            "empty": lambda x: x.astype(np.int64),
            "date": lambda x: pa.array((x + 9000).astype("datetime64[D]")),
            "string": lambda x: np.array([f"k{i:03d}" for i in x]),
        }[kind]
        fkey = conv(k)
        if kind == "nulls":
            fkey = pa.array(fkey, mask=rng.random(n) < 0.1)
        fact = {"k": fkey, "v": v}
        dim = {"dk": conv(dk), "dval": dval}
    for name, t in (("fact", fact), ("dim", dim)):
        d = tmp_path / name
        d.mkdir()
        pq.write_table(pa.table(t), str(d / "part0.parquet"))
    lf = session.read.parquet(str(tmp_path / "fact"))
    rf = session.read.parquet(str(tmp_path / "dim"))
    if kind == "empty":
        rf = rf.filter(col("dval") < 0)
    cols = [c for c in fact] + ([] if how in ("semi", "anti") else ["dval"])
    return lf, rf, on, cols


def _probe_once(session, make_query):
    """(rows sorted, probe mode) of one SPMD run; the mode is read from
    the query's trace."""
    before = spmd.DISPATCH_COUNT
    out = make_query().to_pandas()
    assert spmd.DISPATCH_COUNT > before, "SPMD path was not taken"
    tr = hst.Hyperspace(session).last_trace()
    mode = "direct" if tr.find(sn.JOIN_PROBE_TABLE) else "search"
    return out.sort_values(list(out.columns)).reset_index(drop=True), mode


def _search_only(monkeypatch):
    """Give the broadcast side no stream rows to budget a table from, so
    every probe searches."""
    orig = spmd._prepare_broadcast
    monkeypatch.setattr(
        spmd, "_prepare_broadcast",
        lambda right, pairs, tiny, stream_rows, keys_only=False:
        orig(right, pairs, tiny, 0, keys_only))


class TestDirectProbe:
    """The broadcast probe through a position table gives the rows the
    binary search gives; empty sides and sparse spans keep the search."""

    @pytest.fixture(autouse=True)
    def _traced(self, session):
        session.conf.set(TC.TRACE_ENABLED, "true")

    @pytest.mark.parametrize("kind,how,mode", [
        ("int64", "inner", "direct"), ("int32", "inner", "direct"),
        ("date", "inner", "direct"), ("string", "inner", "direct"),
        ("packed", "inner", "direct"), ("negative", "inner", "direct"),
        ("nulls", "inner", "direct"), ("int64", "left", "direct"),
        ("nulls", "left", "direct"), ("int64", "semi", "direct"),
        ("packed", "semi", "direct"), ("int64", "anti", "direct"),
        ("nulls", "anti", "direct"), ("empty", "inner", "search"),
        ("empty", "left", "search"), ("sparse", "inner", "search"),
        ("sparse", "left", "search"),
    ])
    def test_rows_equal_the_search(self, session, tmp_path, monkeypatch,
                                   kind, how, mode):
        lf, rf, on, cols = _probe_tables(session, tmp_path, kind, how)

        def query():
            return lf.join(rf, on=on, how=how).select(*cols)

        got, got_mode = _probe_once(session, query)
        assert got_mode == mode
        _search_only(monkeypatch)
        searched, searched_mode = _probe_once(session, query)
        assert searched_mode == "search"
        pd.testing.assert_frame_equal(got, searched)
        session.conf.set(IndexConstants.TPU_DISTRIBUTED_ENABLED, "false")
        single = query().to_pandas()
        single = single.sort_values(list(single.columns)) \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(got, single, check_dtype=False)

    def test_direct_and_search_programs_bank_apart(self, session, tmp_path,
                                                   monkeypatch):
        from hyperspace_tpu.serving.program_bank import get_bank

        lf, rf, on, cols = _probe_tables(session, tmp_path, "int64", "inner")

        def query():
            return lf.join(rf, on=on).select(*cols)

        def stages():
            return get_bank().stats()["stages_by_kind"].get("spmd", 0)

        get_bank().clear()  # the other cases banked this query's shape
        _, mode = _probe_once(session, query)
        assert mode == "direct"
        n_direct = stages()
        _probe_once(session, query)
        assert stages() == n_direct  # a repeat reuses its entry
        _search_only(monkeypatch)
        _, mode = _probe_once(session, query)
        assert mode == "search"
        assert stages() == n_direct + 1

    def test_a_new_key_span_reuses_the_program(self, session, tmp_path):
        from hyperspace_tpu.serving.program_bank import get_bank

        lf, rf, on, cols = _probe_tables(session, tmp_path, "int64", "inner")
        _, mode = _probe_once(
            session, lambda: lf.join(rf, on=on).select(*cols))
        assert mode == "direct"
        programs = get_bank().stats()["programs"]
        # As many keys, spanning 91 in place of 80: the table keeps its
        # length class, so the program and its shapes stay the same.
        dk = np.arange(10, 90)
        dk[-1] = 100
        d = tmp_path / "dim2"
        d.mkdir()
        pq.write_table(pa.table({"dk": dk.astype(np.int64),
                                 "dval": np.zeros(80, np.int64)}),
                       str(d / "part0.parquet"))
        rf2 = session.read.parquet(str(d))
        got, mode = _probe_once(
            session, lambda: lf.join(rf2, on=on).select(*cols))
        assert mode == "direct"
        assert get_bank().stats()["programs"] == programs
        assert set(got["k"]) == set(range(10, 89)) | {100}


class TestNullables:
    @pytest.fixture()
    def null_dir(self, tmp_path):
        rng = np.random.default_rng(13)
        n = 4000
        g = rng.integers(0, 20, n).astype(np.float64)
        g[rng.random(n) < 0.1] = np.nan
        v = rng.uniform(0, 100, n)
        v[rng.random(n) < 0.2] = np.nan
        t = pa.table({
            "g": pa.array([None if np.isnan(x) else int(x) for x in g],
                          type=pa.int64()),
            "v": pa.array([None if np.isnan(x) else x for x in v]),
            "w": rng.uniform(0, 1, n),
        })
        d = tmp_path / "nulls"
        d.mkdir()
        pq.write_table(t, str(d / "part0.parquet"))
        return str(d)

    def test_global_agg_null_values(self, session, null_dir):
        df = session.read.parquet(null_dir)
        d, s = run_both(session, lambda: df.agg(
            sum_(col("v")).alias("sv"), count(col("v")).alias("nv"),
            count(None).alias("n")))
        assert_tables_equal(d, s, float_cols=("sv",))

    def test_grouped_nullable_values(self, session, null_dir):
        df = session.read.parquet(null_dir)
        d, s = run_both(
            session,
            lambda: df.select((col("w") * 0).alias("w_bucket"), "v")
            .group_by("w_bucket")
            .agg(sum_(col("v")).alias("sv"), count(col("v")).alias("nv")))
        assert_tables_equal(d, s, float_cols=("sv",))

    def test_nullable_group_key_spmd_only(self, session, null_dir):
        # The single-device executor still raises on nullable group keys;
        # the SPMD path supports them (null = its own group, null-first).
        # Oracle: pandas.
        df = session.read.parquet(null_dir)
        before = spmd.DISPATCH_COUNT
        out = (df.group_by("g")
               .agg(sum_(col("w")).alias("sw"), count(None).alias("n"))
               ).to_arrow()
        assert spmd.DISPATCH_COUNT > before
        pdf = pq.read_table(os.path.join(null_dir, "part0.parquet")).to_pandas()
        ref = (pdf.groupby("g", dropna=False)
               .agg(sw=("w", "sum"), n=("w", "size")).reset_index())
        # null-first ordering in our output; pandas puts NaN last.
        got = out.to_pydict()
        assert got["g"][0] is None
        ref_null = ref[ref.g.isna()]
        assert got["n"][0] == int(ref_null["n"].iloc[0])
        assert abs(got["sw"][0] - float(ref_null["sw"].iloc[0])) < 1e-9
        nn = ref[~ref.g.isna()].sort_values("g")
        assert got["g"][1:] == [int(x) for x in nn["g"]]
        assert got["n"][1:] == [int(x) for x in nn["n"]]
        assert np.allclose(got["sw"][1:], nn["sw"].to_numpy())

    def test_nullable_group_key_negative_values(self, session, tmp_path):
        # Null group must sort FIRST even with negative keys present (nulls
        # are encoded as value 0 on device; only the null-flag being the
        # more significant sort key keeps them ahead of negatives in the
        # host merge).
        rng = np.random.default_rng(14)
        n = 3000
        g = rng.integers(-10, 10, n).astype(np.int64)
        null_at = rng.random(n) < 0.1
        t = pa.table({
            "g": pa.array([None if m else int(x)
                           for x, m in zip(g, null_at)], type=pa.int64()),
            "w": rng.uniform(0, 1, n),
        })
        d = tmp_path / "neg_nulls"
        d.mkdir()
        pq.write_table(t, str(d / "part0.parquet"))
        df = session.read.parquet(str(d))
        before = spmd.DISPATCH_COUNT
        out = (df.group_by("g")
               .agg(sum_(col("w")).alias("sw"), count(None).alias("n"))
               ).to_arrow()
        assert spmd.DISPATCH_COUNT > before
        got = out.to_pydict()
        assert got["g"][0] is None, "null group must come first"
        assert got["g"][1:] == sorted(got["g"][1:])
        pdf = t.to_pandas()
        ref = (pdf.groupby("g", dropna=False)
               .agg(sw=("w", "sum"), n=("w", "size")).reset_index())
        ref_null = ref[ref.g.isna()]
        assert got["n"][0] == int(ref_null["n"].iloc[0])
        assert abs(got["sw"][0] - float(ref_null["sw"].iloc[0])) < 1e-9
        nn = ref[~ref.g.isna()].sort_values("g")
        assert got["g"][1:] == [int(x) for x in nn["g"]]
        assert np.allclose(got["sw"][1:], nn["sw"].to_numpy())


class TestFallbacks:
    def test_disabled_conf(self, session, lineitem_dir):
        session.conf.set(IndexConstants.TPU_DISTRIBUTED_ENABLED, "false")
        li = session.read.parquet(lineitem_dir)
        before = spmd.DISPATCH_COUNT
        li.agg(count(None).alias("n")).to_arrow()
        assert spmd.DISPATCH_COUNT == before

    def test_sort_above_spmd_aggregate(self, session, lineitem_dir):
        # Sort/Limit above the Aggregate run single-device on the merged
        # (small) result; the subtree below still executes SPMD.
        li = session.read.parquet(lineitem_dir)
        d, s = run_both(
            session,
            lambda: li.group_by("l_orderkey")
            .agg(sum_(col("l_price")).alias("sp"))
            .sort(("sp", False)).limit(5))
        assert_tables_equal(d, s, float_cols=("sp",))
