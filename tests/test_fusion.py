"""Whole-plan fusion (execution/fusion.py + fusion_boundaries.py).

Covers: fusion-on vs fusion-off byte-identity over verbatim TPC-H q3/q17
and a bounded TPC-DS sample (the r10 parity-test pattern), per-barrier
fallback behavior (sort, outer join, chunked source, duplicate probe
keys, COUNT DISTINCT), the dispatch-count acceptance (strictly fewer
exec.stage/exec.fused spans fused than staged, second-run compiles = 0
through the ProgramBank), cross-session program sharing (two sessions
compile <= 1.2x one session's count), per-join actuals from fused
regions, the result-cache contracts (a HIT on a fused query is exactly
the {query, serving.cache_lookup} two-span trace; toggling fusion never
orphans warm entries), the frozen boundary-kind registry, and the
distributed tier's fused co-bucketed join+filter+aggregate MeshProgram
(zero resharding collectives on compiled HLO, ONE dispatch).

Sessions run the default conf; stream leaves stay below
``distributed.minStreamRows`` so the single-device fusion tier (not the
SPMD mesh, which keeps right of way) is what executes.
"""

from __future__ import annotations

import datetime
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.api import Hyperspace
from hyperspace_tpu.execution import fusion
from hyperspace_tpu.execution import fusion_boundaries as FB
from hyperspace_tpu.execution import shapes
from hyperspace_tpu.index.constants import IndexConstants
from hyperspace_tpu.plan.expr import col, count_distinct, sum_
from hyperspace_tpu.serving.constants import ServingConstants
from hyperspace_tpu.telemetry import span_names as sn
from hyperspace_tpu.telemetry.constants import TelemetryConstants as TC

import test_tpch_sql as tpch_mod
from goldstandard import tpcds_real

FUSION = IndexConstants.TPU_FUSION_ENABLED


def _fusion(session, on: bool) -> None:
    session.conf.set(FUSION, "true" if on else "false")


def _norm(df: pd.DataFrame) -> pd.DataFrame:
    return tpch_mod._norm(df)


# ---------------------------------------------------------------------------
# Fixtures.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tpch_fusion"))
    session = hst.Session(system_path=os.path.join(root, "indexes"))
    tables = tpch_mod._make_tables(np.random.default_rng(20260804))
    for name, t in tables.items():
        d = os.path.join(root, name)
        os.makedirs(d)
        pq.write_table(t, os.path.join(d, "part0.parquet"))
        session.create_temp_view(name, session.read.parquet(d))
    return session


@pytest.fixture()
def mini(tmp_path):
    """A q3-shaped miniature: lineitem (nullable discount) x orders."""
    rng = np.random.default_rng(11)
    n, n_od = 2400, 300
    li_dir, od_dir = str(tmp_path / "li"), str(tmp_path / "od")
    os.makedirs(li_dir)
    os.makedirs(od_dir)
    disc = rng.uniform(0, 0.1, n).round(3)
    disc_mask = rng.random(n) < 0.1
    pq.write_table(pa.table({
        "l_orderkey": rng.integers(0, n_od, n).astype(np.int64),
        "l_shipdate": rng.integers(0, 1000, n).astype(np.int64),
        "l_extendedprice": rng.uniform(1, 1000, n).round(2),
        "l_discount": pa.array(
            [None if m else float(v) for m, v in zip(disc_mask, disc)],
            type=pa.float64()),
    }), os.path.join(li_dir, "part0.parquet"))
    pq.write_table(pa.table({
        "o_orderkey": np.arange(n_od, dtype=np.int64),
        "o_orderdate": rng.integers(0, 1000, n_od).astype(np.int64),
        "o_shippriority": rng.integers(0, 3, n_od).astype(np.int64),
    }), os.path.join(od_dir, "part0.parquet"))
    session = hst.Session(system_path=str(tmp_path / "indexes"))
    return session, li_dir, od_dir


def _build_q3ish(session, li_dir, od_dir, cut=500):
    li = session.read.parquet(li_dir).filter(col("l_shipdate") > int(cut))
    od = session.read.parquet(od_dir).filter(col("o_orderdate") < 700)
    return (li.join(od, on=col("l_orderkey") == col("o_orderkey"))
            .group_by("o_shippriority")
            .agg(sum_(col("l_extendedprice") * (1 - col("l_discount")))
                 .alias("revenue"))
            .sort("o_shippriority"))


def _on_off(session, build):
    """(fused result, staged result) as pandas, fusion restored to on."""
    _fusion(session, True)
    on = build().to_pandas()
    _fusion(session, False)
    off = build().to_pandas()
    _fusion(session, True)
    return on, off


# ---------------------------------------------------------------------------
# The fused path is taken, and is byte-identical.
# ---------------------------------------------------------------------------

class TestFusedExecution:
    def test_q3ish_fuses_and_matches_staged(self, mini):
        session, li_dir, od_dir = mini
        d0 = fusion.DISPATCH_COUNT
        on, off = _on_off(session, lambda: _build_q3ish(session, li_dir,
                                                        od_dir))
        assert fusion.DISPATCH_COUNT > d0
        pd.testing.assert_frame_equal(on, off)

    def test_fused_region_spans_and_dispatch_counts(self, mini):
        """THE dispatch acceptance: with fusion on, the traced run shows
        one exec.fused span covering the region's nodes and strictly
        fewer total execution spans (exec.stage + exec.fused) than the
        staged run of the same query."""
        session, li_dir, od_dir = mini
        hs = Hyperspace(session)
        q = _build_q3ish(session, li_dir, od_dir)
        q.to_arrow()  # warm compiles untraced
        session.conf.set(TC.TRACE_ENABLED, "true")
        q.to_arrow()
        fused_tr = hs.last_trace()
        _fusion(session, False)
        q.to_arrow()
        staged_tr = hs.last_trace()
        _fusion(session, True)
        session.conf.set(TC.TRACE_ENABLED, "false")
        fused_spans = fused_tr.find(sn.EXEC_FUSED)
        # The main agg+project+join+project+filter region, plus the join
        # SIDE's own project+filter chain region.
        assert len(fused_spans) >= 1
        assert max(s.attrs["fused_nodes"] for s in fused_spans) >= 3
        assert staged_tr.find(sn.EXEC_FUSED) == []
        n_fused = len(fused_tr.find(sn.EXEC_STAGE)) + len(fused_spans)
        n_staged = len(staged_tr.find(sn.EXEC_STAGE))
        assert n_fused < n_staged

    def test_second_run_compiles_zero_through_bank(self, mini):
        session, li_dir, od_dir = mini
        q = _build_q3ish(session, li_dir, od_dir)
        q.to_arrow()  # cold: compiles the region program
        c0 = shapes.compile_count()
        q.to_arrow()
        assert shapes.compile_count() == c0  # warm: bank hit, 0 compiles

    def test_fused_join_records_actuals(self, mini):
        """Fused regions feed the r10/r13 join-actuals store, so the
        join-reorder q-error pairing keeps learning."""
        session, li_dir, od_dir = mini
        session._join_actuals.clear()
        _fusion(session, True)
        _build_q3ish(session, li_dir, od_dir).to_arrow()
        fused_actuals = dict(session._join_actuals)
        assert fused_actuals, "fused region recorded no join actuals"
        session._join_actuals.clear()
        _fusion(session, False)
        _build_q3ish(session, li_dir, od_dir).to_arrow()
        staged_actuals = dict(session._join_actuals)
        _fusion(session, True)
        assert fused_actuals == staged_actuals

    def test_literal_sweep_reuses_one_region_program(self, mini):
        """Literal slots ride as runtime args: shifting a predicate
        literal must not recompile the region."""
        session, li_dir, od_dir = mini
        _build_q3ish(session, li_dir, od_dir, cut=500).to_arrow()
        c0 = shapes.compile_count()
        for cut in (510, 520, 530):
            _build_q3ish(session, li_dir, od_dir, cut=cut).to_arrow()
        assert shapes.compile_count() == c0


# ---------------------------------------------------------------------------
# Parity over verbatim TPC-H and a bounded TPC-DS sample (r10 pattern).
# ---------------------------------------------------------------------------

class TestTpchParity:
    @pytest.mark.parametrize("name", ["q3", "q17"])
    def test_acceptance_queries_identical(self, tpch, name):
        text = dict((c[0], c[1]) for c in tpch_mod._CASES)[name]
        _fusion(tpch, True)
        on = tpch.sql(text).to_pandas()
        _fusion(tpch, False)
        off = tpch.sql(text).to_pandas()
        _fusion(tpch, True)
        pd.testing.assert_frame_equal(on, off)
        assert len(on) > 0

    @pytest.mark.parametrize(
        "name", [c[0] for c in tpch_mod._CASES
                 if c[0] not in ("q3", "q17")])
    def test_full_suite_identical(self, tpch, name):
        text = dict((c[0], c[1]) for c in tpch_mod._CASES)[name]
        on, off = _on_off(tpch, lambda: tpch.sql(text))
        pd.testing.assert_frame_equal(on, off)


TPCDS_EXEC_BUDGET = 6  # deterministic first-K (r10 parity budget pattern)


@pytest.fixture(scope="module")
def tpcds(tmp_path_factory):
    root = tmp_path_factory.mktemp("tpcds_fusion")
    session = hst.Session(system_path=str(root / "indexes"))
    tpcds_real.register_tables(session, str(root / "data"))
    return session


class TestTpcdsParity:
    @pytest.mark.parametrize(
        "name", tpcds_real.QUERY_NAMES[:TPCDS_EXEC_BUDGET])
    def test_sample_identical(self, tpcds, name):
        text = tpcds_real.QUERY_TEXTS[name]
        on, off = _on_off(tpcds, lambda: tpcds.sql(text))
        pd.testing.assert_frame_equal(_norm(on), _norm(off),
                                      check_dtype=False)


# ---------------------------------------------------------------------------
# Barriers and runtime fallbacks (per-kind behavior).
# ---------------------------------------------------------------------------

def _fallbacks():
    return fusion.stats()["fallbacks"]


class TestBarriers:
    def test_sort_barrier_splits_region(self, mini):
        """A Sort inside the chain is a barrier: it executes staged and
        the stages ABOVE it fuse over its output."""
        session, li_dir, od_dir = mini

        def build():
            li = session.read.parquet(li_dir)
            return (li.sort("l_orderkey")
                    .filter(col("l_shipdate") > 300)
                    .filter(col("l_extendedprice") > 10.0)
                    .select("l_orderkey", "l_extendedprice"))
        before = _fallbacks().get(FB.SORT, 0)
        d0 = fusion.DISPATCH_COUNT
        on, off = _on_off(session, build)
        assert _fallbacks().get(FB.SORT, 0) > before
        assert fusion.DISPATCH_COUNT > d0  # the region above still fused
        pd.testing.assert_frame_equal(on, off)

    def test_outer_join_barrier(self, mini):
        session, li_dir, od_dir = mini

        def build():
            li = session.read.parquet(li_dir)
            od = session.read.parquet(od_dir)
            return (li.join(od, on=col("l_orderkey") == col("o_orderkey"),
                            how="left")
                    .filter(col("l_shipdate") > 300)
                    .filter(col("l_extendedprice") > 10.0)
                    .select("l_orderkey", "o_shippriority"))
        before = _fallbacks().get(FB.OUTER_JOIN, 0)
        on, off = _on_off(session, build)
        assert _fallbacks().get(FB.OUTER_JOIN, 0) > before
        pd.testing.assert_frame_equal(on, off)

    def test_chunked_source_falls_back(self, mini):
        """A leaf past the chunk budget belongs to the streaming staged
        path — the fused program must never materialize it whole."""
        session, li_dir, od_dir = mini
        session.conf.set(IndexConstants.TPU_MAX_CHUNK_ROWS, "512")
        try:
            before = _fallbacks().get(FB.CHUNKED_SOURCE, 0)
            on, off = _on_off(
                session, lambda: _build_q3ish(session, li_dir, od_dir))
            assert _fallbacks().get(FB.CHUNKED_SOURCE, 0) > before
            pd.testing.assert_frame_equal(on, off)
        finally:
            session.conf.set(IndexConstants.TPU_MAX_CHUNK_ROWS,
                             IndexConstants.TPU_MAX_CHUNK_ROWS_DEFAULT)

    def test_duplicate_probe_keys_fall_back(self, mini):
        """m:n joins (duplicate side keys) stay with the staged merge
        join, discovered at prep with one host sync."""
        session, li_dir, od_dir = mini

        def build():
            li = session.read.parquet(li_dir)
            li2 = session.read.parquet(li_dir).select(
                col("l_orderkey").alias("r_orderkey"),
                col("l_extendedprice").alias("r_price"))
            return (li.filter(col("l_shipdate") > 800)
                    .join(li2, on=col("l_orderkey") == col("r_orderkey"))
                    .group_by("l_orderkey")
                    .agg(sum_(col("r_price")).alias("s")))
        before = _fallbacks().get(FB.DUPLICATE_PROBE_KEYS, 0)
        on, off = _on_off(session, build)
        assert _fallbacks().get(FB.DUPLICATE_PROBE_KEYS, 0) > before
        pd.testing.assert_frame_equal(
            _norm(on), _norm(off), check_dtype=False)

    def test_count_distinct_barrier(self, mini):
        session, li_dir, od_dir = mini

        def build():
            li = session.read.parquet(li_dir)
            return (li.filter(col("l_shipdate") > 300)
                    .group_by("l_orderkey")
                    .agg(count_distinct(col("l_extendedprice"))
                         .alias("n")))
        before = _fallbacks().get(FB.COUNT_DISTINCT, 0)
        on, off = _on_off(session, build)
        assert _fallbacks().get(FB.COUNT_DISTINCT, 0) > before
        pd.testing.assert_frame_equal(on, off)

    def test_bucket_ordered_stream_falls_back_to_staged(self, tmp_path):
        """A covering-index scan materializes bucket order — the staged
        executor's sort-skipping group-by keeps its home (its counter
        still moves) and the fused tier steps aside at runtime."""
        from hyperspace_tpu.api import IndexConfig
        from hyperspace_tpu.execution import executor as ex
        rng = np.random.default_rng(2)
        d = str(tmp_path / "t")
        os.makedirs(d)
        pq.write_table(pa.table({
            "k": rng.integers(0, 40, 2000).astype(np.int64),
            "w": rng.integers(0, 900, 2000).astype(np.int64),
            "v": rng.uniform(0, 10, 2000),
        }), os.path.join(d, "p.parquet"))
        session = hst.Session(system_path=str(tmp_path / "ix"))
        hs = Hyperspace(session)
        hs.create_index(session.read.parquet(d),
                        IndexConfig("kidx", ["k"], ["w", "v"]))
        session.enable_hyperspace()

        def build():
            return (session.read.parquet(d)
                    .filter(col("k") > 5).filter(col("w") > 100)
                    .group_by("k").agg(sum_(col("v")).alias("sv")))
        before = _fallbacks().get(FB.BUCKET_ORDER, 0)
        g0 = ex.GROUPBY_SORT_SKIPPED
        on, off = _on_off(session, build)
        assert _fallbacks().get(FB.BUCKET_ORDER, 0) > before
        assert ex.GROUPBY_SORT_SKIPPED > g0
        pd.testing.assert_frame_equal(on, off)

    def test_disabled_restores_staged(self, mini):
        session, li_dir, od_dir = mini
        _fusion(session, False)
        try:
            before = _fallbacks().get(FB.DISABLED, 0)
            d0 = fusion.DISPATCH_COUNT
            _build_q3ish(session, li_dir, od_dir).to_arrow()
            assert fusion.DISPATCH_COUNT == d0
            assert _fallbacks().get(FB.DISABLED, 0) > before
        finally:
            _fusion(session, True)


class TestBoundaryRegistry:
    def test_registry_is_the_expected_frozen_vocabulary(self):
        # Referencing every kind here is also what satisfies the
        # scripts/lint.py boundary-coverage gate — like the span-name
        # registry, this vocabulary only changes deliberately.
        assert FB.BOUNDARY_KINDS == frozenset({
            "leaf", "sort", "window", "limit", "union", "aggregate",
            "outer-join", "cross-join", "non-equi-join", "multi-key-join",
            "count-distinct", "unsupported-agg", "unsupported-expr",
            "disabled", "sweep", "region-too-small", "chunked-source",
            "bucket-order", "duplicate-probe-keys", "key-dtype",
            "empty-input", "fused-program-error",
        })


# ---------------------------------------------------------------------------
# ProgramBank integration.
# ---------------------------------------------------------------------------

class TestProgramBankSharing:
    def test_two_sessions_share_fused_regions(self, tmp_path):
        """Acceptance: two sessions running the same warm fused workload
        compile <= 1.2x one session's count (the r11 bank contract,
        extended to region programs)."""
        rng = np.random.default_rng(3)
        li_dir = str(tmp_path / "li")
        os.makedirs(li_dir)
        pq.write_table(pa.table({
            "k": rng.integers(0, 50, 1500).astype(np.int64),
            "v": rng.uniform(0, 100, 1500),
            "w": rng.integers(0, 900, 1500).astype(np.int64),
        }), os.path.join(li_dir, "part0.parquet"))

        def run(session):
            li = session.read.parquet(li_dir)
            return (li.filter(col("w") > 200)
                    .filter(col("v") > 1.0)
                    .group_by("k").agg(sum_(col("v")).alias("s"))
                    ).to_arrow()

        s1 = hst.Session(system_path=str(tmp_path / "ix1"))
        c0 = shapes.compile_count()
        run(s1)
        c1 = shapes.compile_count() - c0
        run(s1)  # warm: second run free
        s2 = hst.Session(system_path=str(tmp_path / "ix2"))
        c2_before = shapes.compile_count()
        run(s2)
        c2 = shapes.compile_count() - c2_before
        assert c1 + c2 <= 1.2 * max(c1, 1), (c1, c2)


# ---------------------------------------------------------------------------
# Result-cache contracts.
# ---------------------------------------------------------------------------

class TestResultCacheContracts:
    def _enable_cache(self, session):
        session.conf.set(ServingConstants.RESULT_CACHE_ENABLED, "true")
        session.conf.set(
            ServingConstants.RESULT_CACHE_MIN_COMPUTE_SECONDS, "0")

    def test_cache_hit_on_fused_query_is_two_span_trace(self, mini):
        """Satellite regression: a result-cache HIT on a fused query must
        still produce the exact {query, serving.cache_lookup} trace, plus
        the answer's fetch — no exec.fused, no exec.stage, no reads."""
        session, li_dir, od_dir = mini
        self._enable_cache(session)
        hs = Hyperspace(session)
        q = _build_q3ish(session, li_dir, od_dir)
        session.conf.set(TC.TRACE_ENABLED, "true")
        q.to_arrow()  # cold: fused execution, admitted
        cold = hs.last_trace()
        q.to_arrow()  # hit
        hit = hs.last_trace()
        session.conf.set(TC.TRACE_ENABLED, "false")
        assert cold.find(sn.EXEC_FUSED) != []
        assert {s.name for s in hit.spans} - {sn.DEVICE_SYNC} == {
            sn.QUERY, sn.CACHE_LOOKUP, sn.RESULT_FETCH}
        assert hit.find(sn.EXEC_FUSED) == []
        assert hit.find(sn.EXEC_STAGE) == []

    def test_fusion_toggle_keeps_warm_entries(self, mini):
        """fusion.* is excluded from the result-cache config hash:
        answers are byte-identical by contract, so toggling the tier must
        not orphan warm entries."""
        session, li_dir, od_dir = mini
        self._enable_cache(session)
        q = _build_q3ish(session, li_dir, od_dir)
        q.to_arrow()  # fused, admitted
        stats0 = session.result_cache.stats()
        _fusion(session, False)
        try:
            q.to_arrow()
        finally:
            _fusion(session, True)
        stats1 = session.result_cache.stats()
        assert stats1["hits"] == stats0["hits"] + 1


# ---------------------------------------------------------------------------
# The distributed tier's fused region (co-bucketed join + consumers).
# ---------------------------------------------------------------------------

class TestDistributedFusedRegion:
    def test_join_filter_agg_single_program_zero_resharding(self):
        """The fused sharded region: the shuffle-free co-bucketed join
        composes with a consumer filter + aggregate in ONE partitioned
        executable — compiled HLO still moves zero rows between devices
        (no all-to-all/all-gather/collective-permute/reduce-scatter) and
        the dispatch counter moves by exactly one."""
        from hyperspace_tpu.execution.columnar import Table
        from hyperspace_tpu.parallel import sharding
        from hyperspace_tpu.parallel.distributed_build import \
            distributed_build_sorted_buckets
        from hyperspace_tpu.parallel.distributed_query import (
            distributed_join_filter_agg, join_filter_agg_collectives)
        from hyperspace_tpu.parallel.mesh import make_mesh
        rng = np.random.default_rng(9)
        n = 2048
        left = Table.from_arrow(pa.table({
            "k": rng.integers(0, 64, n).astype(np.int64),
            "lv": rng.integers(0, 50, n).astype(np.int64),
            "f": rng.integers(0, 100, n).astype(np.int64)}))
        right = Table.from_arrow(pa.table({
            "k": rng.integers(0, 64, n // 2).astype(np.int64),
            "rv": rng.integers(0, 50, n // 2).astype(np.int64)}))
        mesh = make_mesh()
        lt, lvalid, _ = distributed_build_sorted_buckets(
            left, ["k"], 16, mesh)
        rt, rvalid, _ = distributed_build_sorted_buckets(
            right, ["k"], 16, mesh)
        counts = join_filter_agg_collectives(
            lt, lvalid, rt, rvalid, "k", "lv", "rv", "f", 10, 60, mesh)
        assert counts["all-to-all"] == 0, counts
        assert counts["all-gather"] == 0, counts
        assert counts["collective-permute"] == 0, counts
        assert counts["reduce-scatter"] == 0, counts
        assert counts["all-reduce"] >= 1, counts
        d0 = sharding.DISPATCH_COUNT
        cnt, lsum, rsum = distributed_join_filter_agg(
            lt, lvalid, rt, rvalid, "k", "lv", "rv", "f", 10, 60, mesh)
        assert sharding.DISPATCH_COUNT - d0 == 1
        dfl = pd.DataFrame({
            "k": np.asarray(left.column("k").data),
            "lv": np.asarray(left.column("lv").data),
            "f": np.asarray(left.column("f").data)})
        dfr = pd.DataFrame({
            "k": np.asarray(right.column("k").data),
            "rv": np.asarray(right.column("rv").data)})
        joined = dfl[(dfl.f >= 10) & (dfl.f <= 60)].merge(dfr, on="k")
        assert cnt == len(joined)
        assert lsum == joined["lv"].sum()
        assert rsum == joined["rv"].sum()


# ---------------------------------------------------------------------------
# Metrics surface.
# ---------------------------------------------------------------------------

class TestFusionStats:
    def test_metrics_collector_registered(self, mini):
        session, li_dir, od_dir = mini
        _build_q3ish(session, li_dir, od_dir).to_arrow()
        m = Hyperspace(session).metrics()
        assert "fusion" in m["collectors"]
        assert m["collectors"]["fusion"]["fused_executions"] >= 1
        assert isinstance(m["collectors"]["fusion"]["fallbacks"], dict)
        # Region programs are visible in the bank's per-kind breakdown.
        from hyperspace_tpu.serving.program_bank import get_bank
        kinds = get_bank().stats()["stages_by_kind"]
        assert kinds.get("fused-region", 0) >= 1

    def test_datetime_literals_fuse(self, tmp_path):
        """Date-typed slot literals (the q3 shape) ride as runtime args."""
        rng = np.random.default_rng(4)
        d = str(tmp_path / "t")
        os.makedirs(d)
        base = datetime.date(1995, 1, 1)
        pq.write_table(pa.table({
            "ship": pa.array([base + datetime.timedelta(days=int(x))
                              for x in rng.integers(0, 400, 1200)]),
            "price": rng.uniform(1, 100, 1200),
        }), os.path.join(d, "part0.parquet"))
        session = hst.Session(system_path=str(tmp_path / "ix"))
        q = (session.read.parquet(d)
             .filter(col("ship") > datetime.date(1995, 6, 1))
             .filter(col("price") > 5.0)
             .agg(sum_(col("price")).alias("s")))
        d0 = fusion.DISPATCH_COUNT
        on = q.to_pandas()
        assert fusion.DISPATCH_COUNT > d0
        _fusion(session, False)
        off = q.to_pandas()
        _fusion(session, True)
        pd.testing.assert_frame_equal(on, off)
