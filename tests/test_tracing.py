"""Unified query tracing + process metrics registry
(telemetry/trace.py, telemetry/metrics.py, telemetry/span_names.py).

Covers: the span-tree shape of a TPC-H-q3-like run (cold vs
result-cache-hit traces differ exactly at the cache-lookup span), trace
propagation through a multi-threaded ServingFrontend (no cross-query
span leakage, hammer-asserted), the shared literal-sweep span, the
Chrome-trace-event JSON exporter, tracing-off byte-identity + no-op
guarantees, trace_id stamping on every event emitted during a traced
run, the frozen span-name registry, the metrics registry's unified
surface (Hyperspace.metrics()), and the live serving latency histogram.

Sessions run with the default distributed tier; sources are kept below
``distributed.minStreamRows`` so the traced path is the (fast,
deterministic) fused single-device pipeline — the SPMD dispatch span is
covered by tests/test_join_reorder.py's un-pinned actuals tests and the
spmd.compile registry entry below.
"""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.api import Hyperspace
from hyperspace_tpu.index.constants import IndexConstants
from hyperspace_tpu.plan.expr import col, sum_
from hyperspace_tpu.serving.constants import ServingConstants
from hyperspace_tpu.telemetry import span_names as sn
from hyperspace_tpu.telemetry.constants import TelemetryConstants as TC

from conftest import capture_logger  # noqa: E402


N_ORDERS = 400
LI_FILES = 4
LI_ROWS_PER_FILE = 500  # 2000 total: under the 4096 minStreamRows gate


@pytest.fixture()
def q3ish(tmp_path):
    """A miniature TPC-H q3 shape: filtered lineitem x filtered orders,
    grouped revenue, sorted — lineitem split over several files so the
    pooled reader fan-out (and its io.read span) engages."""
    rng = np.random.default_rng(13)
    li_dir = tmp_path / "lineitem"
    os.makedirs(li_dir)
    for i in range(LI_FILES):
        n = LI_ROWS_PER_FILE
        t = pa.table({
            "l_orderkey": pa.array(
                rng.integers(0, N_ORDERS, n).astype(np.int64)),
            "l_shipdate": pa.array(
                rng.integers(0, 1000, n).astype(np.int64)),
            "l_extendedprice": pa.array(rng.uniform(1, 1000, n).round(2)),
            "l_discount": pa.array(rng.uniform(0, 0.1, n).round(3)),
        })
        pq.write_table(t, os.path.join(li_dir, f"part{i}.parquet"))
    od_dir = tmp_path / "orders"
    os.makedirs(od_dir)
    od = pa.table({
        "o_orderkey": pa.array(np.arange(N_ORDERS, dtype=np.int64)),
        "o_orderdate": pa.array(
            rng.integers(0, 1000, N_ORDERS).astype(np.int64)),
        "o_shippriority": pa.array(
            rng.integers(0, 3, N_ORDERS).astype(np.int64)),
    })
    pq.write_table(od, os.path.join(od_dir, "part0.parquet"))
    session = hst.Session(system_path=str(tmp_path / "indexes"))
    session.conf.set(IndexConstants.INDEX_NUM_BUCKETS, 4)
    return session, str(li_dir), str(od_dir)


def _build_q3(session, li_dir, od_dir, ship_cut=500):
    li = session.read.parquet(li_dir).filter(
        col("l_shipdate") > int(ship_cut))
    od = session.read.parquet(od_dir).filter(col("o_orderdate") < 700)
    return (li.join(od, on=col("l_orderkey") == col("o_orderkey"))
            .group_by("o_shippriority")
            .agg(sum_(col("l_extendedprice") * (1 - col("l_discount")))
                 .alias("revenue"))
            .sort("o_shippriority"))


def _tracing(session, on: bool) -> None:
    session.conf.set(TC.TRACE_ENABLED, "true" if on else "false")


# ---------------------------------------------------------------------------
# Span-tree shape.
# ---------------------------------------------------------------------------

class TestTraceShape:
    def test_q3_cold_trace_covers_every_boundary(self, q3ish):
        session, li_dir, od_dir = q3ish
        session.enable_hyperspace()
        hs = Hyperspace(session)
        q = _build_q3(session, li_dir, od_dir)
        q.to_arrow()  # warm (compiles) untraced
        # Drop the warm-up read's buffers so the traced run performs
        # real pooled I/O — a buffer-pool hit would skip io.read spans.
        from hyperspace_tpu.execution import buffer_pool
        buffer_pool.get_pool().clear()
        _tracing(session, True)
        q.to_arrow()
        tr = hs.last_trace()
        assert tr is not None and tr.dropped == 0
        names = {s.name for s in tr.spans}
        # The acceptance set: optimize, rewrite, per-stage execution,
        # program-bank lookups, and pooled I/O reads, under one root.
        assert sn.QUERY in names
        assert sn.PLAN_NORMALIZE in names
        assert sn.INDEX_REWRITE in names
        assert sn.EXEC_STAGE in names
        assert sn.BANK_LOOKUP in names
        assert sn.IO_READ in names
        # Tree integrity: exactly one root, every parent id resolves,
        # every span carries the trace's id.
        roots = [s for s in tr.spans if s.parent_id is None]
        assert [r.name for r in roots] == [sn.QUERY]
        ids = {s.span_id for s in tr.spans}
        for s in tr.spans:
            assert s.trace_id == tr.trace_id
            assert s.parent_id is None or s.parent_id in ids
        # exec.stage spans nest with the plan tree (a join stage has a
        # child stage), and the io.read span hangs off a scan stage.
        exec_ids = {s.span_id for s in tr.spans if s.name == sn.EXEC_STAGE}
        assert any(s.parent_id in exec_ids for s in tr.spans
                   if s.name == sn.EXEC_STAGE)
        assert any(s.parent_id in exec_ids for s in tr.spans
                   if s.name == sn.IO_READ)
        # Node attributes ride the stage spans. The Join (and the filter
        # chain under it) executes inside the whole-plan FUSED region by
        # default — its exec.fused span hangs off the Aggregate stage and
        # reports how many plan nodes it collapsed.
        stage_nodes = {s.attrs.get("node") for s in tr.spans
                       if s.name == sn.EXEC_STAGE}
        assert {"Aggregate", "Sort"} <= stage_nodes
        fused = tr.find(sn.EXEC_FUSED)
        assert fused and max(s.attrs["fused_nodes"] for s in fused) >= 2
        assert any(s.parent_id in exec_ids for s in fused)
        # Inside the rewrite: the index manager's lookup (no index, so
        # no source check); the fused region's one scalar is a device
        # sync under it; the answer's fetch hangs off the root.
        by_id = {s.span_id: s for s in tr.spans}
        rewrite = tr.find(sn.INDEX_REWRITE)[0]
        assert any(s.parent_id == rewrite.span_id
                   for s in tr.find(sn.INDEX_LOOKUP))
        fused_ids = {s.span_id for s in fused}
        assert any(s.parent_id in fused_ids and s.attrs["node"] ==
                   "fused.groups" for s in tr.find(sn.DEVICE_SYNC))
        (fetch,) = tr.find(sn.RESULT_FETCH)
        assert by_id[fetch.parent_id].name == sn.QUERY

    def test_cold_vs_hit_traces_differ_at_cache_lookup(self, q3ish):
        session, li_dir, od_dir = q3ish
        session.conf.set(ServingConstants.RESULT_CACHE_ENABLED, "true")
        session.conf.set(
            ServingConstants.RESULT_CACHE_MIN_COMPUTE_SECONDS, "0")
        hs = Hyperspace(session)
        q = _build_q3(session, li_dir, od_dir)
        _tracing(session, True)
        q.to_arrow()
        cold = hs.last_trace()
        q.to_arrow()
        hit = hs.last_trace()
        assert cold is not None and hit is not None
        assert cold.trace_id != hit.trace_id
        cold_lookup = cold.find(sn.CACHE_LOOKUP)
        hit_lookup = hit.find(sn.CACHE_LOOKUP)
        assert len(cold_lookup) == len(hit_lookup) == 1
        assert cold_lookup[0].attrs["hit"] is False
        assert hit_lookup[0].attrs["hit"] is True
        assert hit_lookup[0].attrs["tier"] in ("device", "host")
        # The hit trace is EXACTLY root + cache lookup + the answer's
        # fetch: no optimize, no execution, no reads. The cold trace
        # carries the rest.
        assert {s.name for s in hit.spans} - {sn.DEVICE_SYNC} == {
            sn.QUERY, sn.CACHE_LOOKUP, sn.RESULT_FETCH}
        assert hit.find(sn.EXEC_STAGE) == []
        assert cold.find(sn.EXEC_STAGE) != []

    def test_max_spans_cap_drops_not_grows(self, q3ish):
        session, li_dir, od_dir = q3ish
        session.conf.set(TC.TRACE_MAX_SPANS, "4")
        _tracing(session, True)
        q = _build_q3(session, li_dir, od_dir)
        q.to_arrow()
        tr = Hyperspace(session).last_trace()
        assert len(tr.spans) <= 4
        assert tr.dropped > 0
        # The capped trace still renders and exports.
        assert json.loads(tr.to_chrome_json())["otherData"][
            "dropped_spans"] == tr.dropped


# ---------------------------------------------------------------------------
# Tracing-off contract.
# ---------------------------------------------------------------------------

class TestTracingOff:
    def test_off_is_byte_identical_and_traceless(self, q3ish):
        # Tracing defaults ON since the observability round; the off
        # CONTRACT (hard no-op, byte identity) is now an explicit
        # opt-out.
        session, li_dir, od_dir = q3ish
        _tracing(session, False)
        hs = Hyperspace(session)
        q = _build_q3(session, li_dir, od_dir)
        off = q.to_arrow()
        assert hs.last_trace() is None
        _tracing(session, True)
        on = q.to_arrow()
        assert hs.last_trace() is not None
        _tracing(session, False)
        off2 = q.to_arrow()
        assert on.equals(off)
        assert off2.equals(off)
        # Turning tracing back off leaves the LAST trace readable but
        # records no new one (its id stays put).
        tid = hs.last_trace().trace_id
        q.to_arrow()
        assert hs.last_trace().trace_id == tid

    def test_off_events_carry_no_stamp(self, q3ish):
        session, li_dir, od_dir = q3ish
        _tracing(session, False)
        session.conf.set(IndexConstants.EVENT_LOGGER_CLASS,
                         "tests.conftest.CaptureLogger")
        sink = capture_logger()
        sink.events.clear()
        _build_q3(session, li_dir, od_dir).to_arrow()
        assert sink.events
        assert all(e.trace_id == "" and e.span_id == ""
                   for e in sink.events)


# ---------------------------------------------------------------------------
# Event stamping.
# ---------------------------------------------------------------------------

class TestEventStamping:
    def test_every_event_in_a_traced_run_is_stamped(self, q3ish):
        session, li_dir, od_dir = q3ish
        session.conf.set(IndexConstants.EVENT_LOGGER_CLASS,
                         "tests.conftest.CaptureLogger")
        session.conf.set(ServingConstants.RESULT_CACHE_ENABLED, "true")
        session.conf.set(
            ServingConstants.RESULT_CACHE_MIN_COMPUTE_SECONDS, "0")
        hs = Hyperspace(session)
        sink = capture_logger()
        q = _build_q3(session, li_dir, od_dir)
        _tracing(session, True)
        sink.events.clear()
        q.to_arrow()   # miss + admit (+ io reads, bank traffic)
        miss_tid = hs.last_trace().trace_id
        q.to_arrow()   # hit
        hit_tid = hs.last_trace().trace_id
        assert sink.events
        classes = {type(e).__name__ for e in sink.events}
        # Several distinct event classes fired, and EVERY one of them
        # carries the trace stamp of the query that emitted it.
        assert "ResultCacheMissEvent" in classes
        assert "ResultCacheHitEvent" in classes
        assert "IoReadEvent" in classes
        assert len(classes) >= 3
        for e in sink.events:
            assert e.trace_id in (miss_tid, hit_tid), type(e).__name__
            assert e.span_id != ""
        hit_events = [e for e in sink.events
                      if type(e).__name__ == "ResultCacheHitEvent"]
        assert all(e.trace_id == hit_tid for e in hit_events)


# ---------------------------------------------------------------------------
# Chrome trace-event export.
# ---------------------------------------------------------------------------

class TestChromeExport:
    def test_export_matches_trace_event_schema(self, q3ish):
        session, li_dir, od_dir = q3ish
        _tracing(session, True)
        _build_q3(session, li_dir, od_dir).to_arrow()
        tr = Hyperspace(session).last_trace()
        doc = json.loads(tr.to_chrome_json())
        assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
        events = doc["traceEvents"]
        assert len(events) == len(tr.spans)
        ids = set()
        for ev in events:
            # The complete-event ("X") schema chrome://tracing/Perfetto
            # require: name/cat/ph/ts/dur/pid/tid, args carrying the
            # span tree.
            assert {"name", "cat", "ph", "ts", "dur", "pid",
                    "tid"} <= set(ev)
            assert ev["ph"] == "X"
            assert ev["cat"] == "hyperspace"
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert isinstance(ev["pid"], int)
            assert ev["name"] in sn.SPAN_NAMES
            ids.add(ev["args"]["span_id"])
        for ev in events:
            parent = ev["args"].get("parent_id")
            assert parent is None or parent in ids
        assert doc["otherData"]["trace_id"] == tr.trace_id


# ---------------------------------------------------------------------------
# Serving frontend: propagation, leakage, the shared sweep span.
# ---------------------------------------------------------------------------

class TestServingPropagation:
    def _frontend(self, session, concurrency, batching: bool):
        from hyperspace_tpu.serving.frontend import ServingFrontend
        session.conf.set(
            ServingConstants.SERVING_MAX_CONCURRENCY, str(concurrency))
        session.conf.set(ServingConstants.SERVING_BATCHING_ENABLED,
                         "true" if batching else "false")
        return ServingFrontend(session)

    def test_8_thread_hammer_no_cross_query_leakage(self, q3ish):
        session, li_dir, od_dir = q3ish
        _tracing(session, True)
        fe = self._frontend(session, 8, batching=False)
        for _round in range(3):
            queries = [_build_q3(session, li_dir, od_dir,
                                 ship_cut=100 + 40 * i)
                       for i in range(8)]
            pend = [fe.submit(q) for q in queries]
            for p in pend:
                p.result(timeout=300)
            traces = [p.context.trace for p in pend]
            assert all(t is not None for t in traces)
            assert len({t.trace_id for t in traces}) == 8
            shapes = []
            for t in traces:
                # One root per query, every span stamped with ITS
                # trace's id — a leaked span would land in another
                # trace's list with a foreign structure.
                roots = [s for s in t.spans if s.parent_id is None]
                assert [r.name for r in roots] == [sn.QUERY]
                assert all(s.trace_id == t.trace_id for s in t.spans)
                # (Compiles land in whichever query happened to pay
                # them, so they are no part of the structure.)
                shapes.append(frozenset(s.name for s in t.spans
                                        if s.name != sn.XLA_COMPILE))
            # Same query structure -> same span vocabulary, all 8 ways.
            assert len(set(shapes)) == 1

    def test_literal_sweep_shares_one_trace(self, q3ish):
        session, li_dir, od_dir = q3ish
        _tracing(session, True)
        session.conf.set(ServingConstants.SERVING_BATCHING_WINDOW, "0.4")
        fe = self._frontend(session, 1, batching=True)
        variants = [_build_q3(session, li_dir, od_dir,
                              ship_cut=200 + 10 * i) for i in range(4)]
        serial = [v.to_pandas() for v in variants]
        pend = [fe.submit(v) for v in variants]
        frames = [p.result(timeout=300).to_pandas() for p in pend]
        for a, b in zip(serial, frames):
            assert a.round(6).equals(b.round(6))
        batched = [p for p in pend if p.batched]
        if len(batched) >= 2:  # the window raced shut on slow machines
            traces = {id(p.context.trace): p.context.trace
                      for p in batched}
            assert len(traces) == 1  # ONE shared trace for the sweep
            tr = next(iter(traces.values()))
            sweeps = tr.find(sn.SERVING_SWEEP)
            assert len(sweeps) == 1
            members = tr.find(sn.QUERY)
            assert len(members) == len(batched)
            assert all(m.parent_id == sweeps[0].span_id
                       for m in members)
            assert sweeps[0].attrs["members"] == len(batched)

    def test_live_latency_histogram_feeds_metrics(self, q3ish):
        session, li_dir, od_dir = q3ish
        hs = Hyperspace(session)
        fe = self._frontend(session, 2, batching=False)
        before = Hyperspace(session).metrics()["histograms"].get(
            "serving.latency_ms", {}).get("total_count", 0)
        pend = [fe.submit(_build_q3(session, li_dir, od_dir,
                                    ship_cut=300 + i)) for i in range(5)]
        for p in pend:
            p.result(timeout=300)
        hist = hs.metrics()["histograms"]["serving.latency_ms"]
        assert hist["total_count"] >= before + 5
        assert hist["count"] >= 5
        assert 0 <= hist["p50"] <= hist["p99"]
        assert hist["qps"] > 0
        assert hist["window_s"] == \
            session.hs_conf.telemetry_serving_latency_window()


# ---------------------------------------------------------------------------
# The frozen span-name registry.
# ---------------------------------------------------------------------------

class TestSpanRegistry:
    def test_registry_is_the_expected_frozen_vocabulary(self):
        # Referencing every value here is also what satisfies the
        # scripts/lint.py span-coverage gate — like this list, the
        # registry only changes deliberately.
        assert sn.SPAN_NAMES == frozenset({
            "query", "plan.normalize", "optimize.join_reorder",
            "rewrite.index_rules", "serving.cache_lookup",
            "bank.lookup", "bank.compile", "exec.stage", "exec.fused",
            "io.read", "io.prefetch", "spmd.dispatch", "spmd.compile",
            "serving.sweep", "ingest.append", "ingest.commit",
            "ingest.compact", "artifact.load", "artifact.export",
            "artifact.warmup", "cluster.forward", "cluster.broadcast",
            "cluster.gather", "ingest.source", "ingest.wave",
            "rewrite.index_lookup", "rewrite.source_check",
            "skipping.probe", "spmd.gate", "spmd.prepare", "spmd.launch",
            "spmd.merge", "device.sync", "result.fetch", "xla.compile",
            "join.probe_table",
        })

    def test_join_reorder_span_appears_when_enabled(self, q3ish):
        from hyperspace_tpu.optimizer.constants import OptimizerConstants
        session, li_dir, od_dir = q3ish
        session.conf.set(OptimizerConstants.JOIN_REORDER_ENABLED, "true")
        _tracing(session, True)
        _build_q3(session, li_dir, od_dir).to_arrow()
        tr = Hyperspace(session).last_trace()
        assert tr.find(sn.JOIN_REORDER)


# ---------------------------------------------------------------------------
# Metrics registry: one surface over every subsystem.
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_metrics_covers_the_five_stats_surfaces(self, q3ish):
        session, li_dir, od_dir = q3ish
        session.conf.set(ServingConstants.RESULT_CACHE_ENABLED, "true")
        hs = Hyperspace(session)
        _build_q3(session, li_dir, od_dir).to_arrow()
        m = hs.metrics()
        assert {"counters", "gauges", "histograms",
                "collectors"} <= set(m)
        cols = m["collectors"]
        # Every counter previously reachable via the five stats APIs.
        assert cols["io"] == hs.io_stats()
        for key in ("pooled_reads", "read_tasks", "read_bytes",
                    "read_seconds", "wait_seconds", "pool_threads"):
            assert key in cols["io"]
        bank = cols["program_bank"]
        for key in ("stages", "programs", "hits", "misses", "evictions"):
            assert key in bank
        # Naming unification complete: canonical `evictions` only — the
        # deprecated pre-r13 `stage_evictions` alias was removed.
        assert "stage_evictions" not in bank
        rc = cols["result_cache"]
        assert set(rc["result_cache"]) >= {"hits", "misses", "evictions"}
        assert "sql_plan_cache" in rc
        spmd = cols["spmd"]
        for key in ("enabled", "mesh_devices", "query_dispatches",
                    "mesh_programs_compiled"):
            assert key in spmd
        assert "serving" in cols

    def test_histogram_window_slides(self):
        from hyperspace_tpu.telemetry.metrics import SlidingHistogram
        h = SlidingHistogram(window_s=10.0)
        h.record(5.0, now=100.0)
        h.record(7.0, now=101.0)
        h.record(15.0, now=104.0)
        snap = h.snapshot(now=105.0)
        # Upper-index percentile convention (matches bench's _pct).
        assert snap["count"] == 3 and snap["p50"] == 7.0
        assert snap["max"] == 15.0
        snap = h.snapshot(now=112.0)  # the two oldest aged out
        assert snap["count"] == 1 and snap["p50"] == 15.0
        assert snap["total_count"] == 3

    def test_histogram_truncation_keeps_qps_honest(self):
        """Past max_samples the oldest in-window samples drop; the
        snapshot must flag it and rate over the RETAINED span instead of
        silently under-reporting QPS (the high-load regime the live
        histogram exists for)."""
        from hyperspace_tpu.telemetry.metrics import SlidingHistogram
        h = SlidingHistogram(window_s=60.0, max_samples=16)
        for i in range(64):  # 64 samples over 6.3s, all in-window
            h.record(float(i), now=100.0 + i * 0.1)
        snap = h.snapshot(now=106.4)
        assert snap["truncated"] is True
        assert snap["count"] == 16
        # Rate over the ~1.5s the retained samples span, NOT count/60.
        assert snap["qps"] > 5.0
        assert snap["p50"] >= 48.0  # percentiles over the newest samples

    def test_histogram_window_owned_not_thrashed(self):
        """Recording-side histogram() asks (window_s=None) never
        re-window a live instrument; only an explicit owner ask does."""
        from hyperspace_tpu.telemetry.metrics import MetricsRegistry
        reg = MetricsRegistry()
        h = reg.histogram("lat", 30.0)
        assert reg.histogram("lat") is h          # recording-side ask
        assert h.window_s == 30.0                 # ... left it alone
        reg.histogram("lat", 10.0)                # owner re-window
        assert h.window_s == 10.0

    def test_tracing_toggle_keeps_result_cache_warm(self, q3ish):
        """telemetry.* keys are excluded from the result-cache config
        hash (like serving.*): flipping tracing on must serve the warm
        entry, not orphan it."""
        session, li_dir, od_dir = q3ish
        session.conf.set(ServingConstants.RESULT_CACHE_ENABLED, "true")
        session.conf.set(
            ServingConstants.RESULT_CACHE_MIN_COMPUTE_SECONDS, "0")
        q = _build_q3(session, li_dir, od_dir)
        q.to_arrow()  # miss + admit, untraced
        _tracing(session, True)
        q.to_arrow()  # must HIT the entry admitted before the toggle
        hs = Hyperspace(session)
        tr = hs.last_trace()
        lookup = tr.find(sn.CACHE_LOOKUP)
        assert len(lookup) == 1 and lookup[0].attrs["hit"] is True

    def test_collector_failure_is_contained(self):
        from hyperspace_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()

        def boom():
            raise RuntimeError("broken stats source")

        reg.register_collector("broken", boom)
        reg.counter_add("fine", 2)
        snap = reg.snapshot()
        assert snap["collectors"]["broken"] == {"error": "collector failed"}
        assert snap["counters"]["fine"] == 2


# ---------------------------------------------------------------------------
# Explain surfacing + profiler hook.
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_explain_renders_trace_timeline(self, q3ish):
        from hyperspace_tpu.plananalysis.explain import explain_string
        session, li_dir, od_dir = q3ish
        q = _build_q3(session, li_dir, od_dir)
        text = explain_string(session, q.plan)
        assert "Trace:" not in text  # no traced run yet -> untouched
        _tracing(session, True)
        q.to_arrow()
        text = explain_string(session, q.plan)
        assert "Trace:" in text
        section = text.split("Trace:")[-1]
        assert "query" in section
        assert "exec.stage" in section
        assert "self" in section  # self-times rendered

    def test_profiler_brackets_exactly_one_query(self, q3ish, tmp_path):
        session, li_dir, od_dir = q3ish
        from hyperspace_tpu.telemetry import trace as trace_mod
        prof_dir = str(tmp_path / "profile")
        trace_mod.reset_profiler()
        session.conf.set(TC.PROFILER_ENABLED, "true")
        session.conf.set(TC.PROFILER_DIR, prof_dir)
        q = _build_q3(session, li_dir, od_dir)
        try:
            q.to_arrow()
        finally:
            session.conf.set(TC.PROFILER_ENABLED, "false")
        assert os.path.isdir(prof_dir)  # a capture landed
        captured = set()
        for r, _d, files in os.walk(prof_dir):
            captured.update(files)
        before = set(captured)
        # Disarmed (one-shot consumed): a second run adds nothing.
        session.conf.set(TC.PROFILER_ENABLED, "true")
        try:
            q.to_arrow()
        finally:
            session.conf.set(TC.PROFILER_ENABLED, "false")
        after = set()
        for r, _d, files in os.walk(prof_dir):
            after.update(files)
        assert after == before


# ---------------------------------------------------------------------------
# The indexed-query host path: rewrite, skipping probe, SPMD gate and
# dispatch, device syncs, result fetch, compiles; the profiler's clock.
# ---------------------------------------------------------------------------

Q6_FILES = 6
Q6_ROWS_PER_FILE = 700


@pytest.fixture()
def q6ish(tmp_path):
    """A miniature TPC-H Q6 lake: lineitem sorted by ship date over
    several files, a MinMax data-skipping index on the date, and the
    SPMD tier forced on over one device, as on one chip."""
    rng = np.random.default_rng(6)
    li_dir = tmp_path / "lineitem"
    os.makedirs(li_dir)
    for i in range(Q6_FILES):
        n = Q6_ROWS_PER_FILE
        pq.write_table(pa.table({
            "l_shipdate": pa.array(np.sort(
                rng.integers(i * 100, i * 100 + 100, n)).astype(np.int32)),
            "l_discount": pa.array(rng.uniform(0, 0.1, n).round(2)),
            "l_quantity": pa.array(rng.integers(1, 50, n).astype(np.float64)),
            "l_extendedprice": pa.array(rng.uniform(1, 1000, n).round(2)),
        }), os.path.join(li_dir, f"part{i}.parquet"))
    session = hst.Session(system_path=str(tmp_path / "indexes"), conf={
        IndexConstants.TPU_DISTRIBUTED_SINGLE_DEVICE: "on",
        IndexConstants.TPU_DISTRIBUTED_MESH_MAX_DEVICES: "1",
        IndexConstants.TPU_DISTRIBUTED_MIN_STREAM_ROWS: "0",
        TC.TRACE_SAMPLE_RATE: "1.0"})
    from hyperspace_tpu.api import DataSkippingIndexConfig, MinMaxSketch
    Hyperspace(session).create_index(
        session.read.parquet(str(li_dir)),
        DataSkippingIndexConfig("li_ship_skip", [MinMaxSketch("l_shipdate")]))
    session.enable_hyperspace()
    return session, str(li_dir)


def _build_q6(session, li_dir, year=2):
    lo = year * 100
    return (session.read.parquet(li_dir)
            .filter((col("l_shipdate") >= lo) & (col("l_shipdate") < lo + 100)
                    & (col("l_quantity") < 24))
            .agg(sum_(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def _children(tr, parent):
    return [s for s in tr.spans if s.parent_id == parent.span_id]


class TestHostPathSpans:
    def test_q6_skipping_tree(self, q6ish):
        session, li_dir = q6ish
        hs = Hyperspace(session)
        q = _build_q6(session, li_dir)
        q.to_arrow()  # warm (compiles) untraced
        _tracing(session, True)
        q.to_arrow()
        tr = hs.last_trace()
        assert tr is not None and tr.dropped == 0
        (root,) = [s for s in tr.spans if s.parent_id is None]
        assert root.name == sn.QUERY
        # query -> rewrite.index_rules -> {index lookup, source check,
        # skipping probe}
        (rewrite,) = tr.find(sn.INDEX_REWRITE)
        assert rewrite.parent_id == root.span_id
        under_rewrite = {s.name for s in _children(tr, rewrite)}
        assert {sn.INDEX_LOOKUP, sn.SOURCE_CHECK,
                sn.SKIPPING_PROBE} <= under_rewrite
        (probe,) = tr.find(sn.SKIPPING_PROBE)
        assert probe.attrs["files"] == Q6_FILES
        assert probe.attrs["kept"] == 1
        # The gate decides, then the dispatch prepares, launches, syncs
        # and merges.
        assert tr.find(sn.SPMD_GATE)
        (dispatch,) = tr.find(sn.SPMD_DISPATCH)
        under_dispatch = _children(tr, dispatch)
        assert [s.name for s in under_dispatch] == [
            sn.SPMD_PREPARE, sn.SPMD_LAUNCH, sn.SPMD_MERGE]
        merge = under_dispatch[-1]
        syncs = [s for s in tr.find(sn.DEVICE_SYNC)
                 if s.parent_id == merge.span_id]
        assert syncs and all(s.attrs["node"] == "spmd.merge" for s in syncs)
        # Spans nest inside their parents.
        by_id = {s.span_id: s for s in tr.spans}
        for s in tr.spans:
            if s.parent_id is not None:
                p = by_id[s.parent_id]
                assert p.start_perf <= s.start_perf
                assert s.end_perf <= p.end_perf
        # The answer's fetch is the root's last child: the query ends
        # after it.
        (fetch,) = tr.find(sn.RESULT_FETCH)
        assert fetch.parent_id == root.span_id
        assert _children(tr, root)[-1] is fetch
        assert [s.attrs["node"] for s in _children(tr, fetch)] == ["result"]

    def test_tracing_off_records_nothing_and_same_answer(self, q6ish):
        session, li_dir = q6ish
        hs = Hyperspace(session)
        q = _build_q6(session, li_dir)
        _tracing(session, False)
        off = q.to_arrow()
        assert hs.last_trace() is None
        _tracing(session, True)
        on = q.to_arrow()
        assert hs.last_trace() is not None
        _tracing(session, False)
        off2 = q.to_arrow()
        assert on.equals(off) and off2.equals(off)

    def test_forced_compile_is_one_xla_compile_span(self, q6ish):
        import jax
        import jax.numpy as jnp
        from hyperspace_tpu.serving.context import QueryContext
        from hyperspace_tpu.telemetry import trace as trace_mod

        session, _ = q6ish
        _tracing(session, True)

        def forced_compile_probe(x):
            return jnp.cumsum(x) * 3

        ctx = QueryContext.for_session(session)
        with trace_mod.query_trace(session, ctx):
            jax.jit(forced_compile_probe)(np.arange(11.0))
        spans = [s for s in ctx.trace.find(sn.XLA_COMPILE)
                 if "forced_compile_probe" in s.attrs["node"]]
        assert len(spans) == 1
        (s,) = spans
        assert s.attrs["node"] == s.attrs["fun_name"]
        assert s.attrs["seconds"] > 0 and s.attrs["cached"] is False
        assert s.end_perf - s.start_perf == pytest.approx(
            s.attrs["seconds"], abs=1e-6)

    def test_compile_listener_marks_cache_loads(self, q6ish):
        from hyperspace_tpu.execution import shapes
        from hyperspace_tpu.serving.context import QueryContext
        from hyperspace_tpu.telemetry import trace as trace_mod

        session, _ = q6ish
        _tracing(session, True)
        ctx = QueryContext.for_session(session)
        with trace_mod.query_trace(session, ctx):
            shapes._on_compile_event(shapes._CACHE_HIT_EVENT, 0.001)
            shapes._on_compile_event(shapes._COMPILE_EVENT, 0.002,
                                     fun_name="jit(loaded)")
            shapes._on_compile_event(shapes._COMPILE_EVENT, 0.003,
                                     fun_name="jit(compiled)")
        got = {s.attrs["node"]: s.attrs["cached"]
               for s in ctx.trace.find(sn.XLA_COMPILE)}
        assert got == {"jit(loaded)": True, "jit(compiled)": False}
        # No trace active: the listener records nothing and leaves no
        # mark for a later traced compile to inherit.
        before = shapes.compile_count()
        shapes._on_compile_event(shapes._CACHE_HIT_EVENT, 0.001)
        shapes._on_compile_event(shapes._COMPILE_EVENT, 0.002,
                                 fun_name="jit(untraced)")
        assert shapes.compile_count() == before + 1
        assert not getattr(shapes._cache_hit, "seen", False)


def _host_events(xplane_path):
    """(name, start_ns, end_ns, stats) of every host-plane event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)))
    return out


def _capture(tmp_path, run):
    """Profile ``run()`` under a ``bench.window`` annotation opened at a
    known perf_counter instant, as the benchmark does; return the host
    events and that instant."""
    import glob
    import time

    import jax

    out_dir = str(tmp_path / "xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        win = jax.profiler.TraceAnnotation("bench.window")
        perf0 = time.perf_counter()
        win.__enter__()
        run()
        win.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return _host_events(path), perf0


class TestProfilerClock:
    def test_program_spans_are_host_annotations_on_one_clock(
            self, q6ish, tmp_path):
        from hyperspace_tpu.telemetry.trace import span_label

        session, li_dir = q6ish
        hs = Hyperspace(session)
        q = _build_q6(session, li_dir)
        q.to_arrow()  # warm
        _tracing(session, True)
        events, perf0 = _capture(tmp_path, q.to_arrow)
        tr = hs.last_trace()
        (win,) = [e for e in events if e[0] == "bench.window"]
        # The harness's one-anchor mapping of perf_counter onto the
        # profiler's clock.
        offset = win[1] - perf0 * 1e9
        by_label = {}
        for name, s, e, _ in events:
            by_label.setdefault(name, []).append((s, e))
        # Spans recorded after the fact (add_span) carry no annotation.
        opened = [s for s in tr.spans if s.name != sn.XLA_COMPILE]
        assert len(opened) >= 10
        for sp in opened:
            label = span_label(sp.name, sp.attrs)
            assert label in by_label, label
            mapped = sp.start_perf * 1e9 + offset
            nearest = min(abs(mapped - s) for s, _ in by_label[label])
            assert nearest < 0.5e6, (label, nearest)
        # The SPMD program's device ops carry its stable module name.
        modules = {st.get("hlo_module") for _, _, _, st in events}
        assert "jit_spmd_agg" in modules

    def test_no_annotation_without_a_trace(self, q6ish, tmp_path):
        session, li_dir = q6ish
        q = _build_q6(session, li_dir)
        q.to_arrow()  # warm
        _tracing(session, False)
        events, _ = _capture(tmp_path, q.to_arrow)
        names = {e[0] for e in events}
        assert not names & (sn.SPAN_NAMES - {sn.QUERY})
        assert sn.QUERY not in names


class TestStableProgramNames:
    def test_spmd_program_and_phase_scopes(self, q6ish):
        from hyperspace_tpu.execution import spmd

        session, li_dir = q6ish
        _build_q6(session, li_dir, year=3).to_arrow()
        prog, sig = spmd._LAST_PROGRAM
        hlo = prog._compiled[sig][0].as_text()
        assert "HloModule jit_spmd_agg" in hlo
        # Ops carry the program's scope and their stage's (the per-device
        # body runs under a vmap on one device).
        op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
        assert any(n.startswith("jit(spmd_agg)/spmd_agg/")
                   and "stage0_filter" in n for n in op_names)

    def test_fused_region_name(self, q3ish, tmp_path):
        session, li_dir, od_dir = q3ish
        q = _build_q3(session, li_dir, od_dir)
        q.to_arrow()  # warm: the region compiles here
        events, _ = _capture(tmp_path, q.to_arrow)
        modules = {st.get("hlo_module") for _, _, _, st in events}
        assert "jit_fused_region" in modules
