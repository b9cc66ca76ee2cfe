"""Session + DataFrame API.

The SparkSession analogue: holds the conf, the pluggable source-provider
manager, and the optimizer-rule batch that `enable_hyperspace()` injects
(parity: package.scala:35-75 — the reference splices JoinIndexRule ::
FilterIndexRule into experimentalMethods.extraOptimizations).

DataFrames are thin wrappers over the logical plan IR; `collect()` runs
analysis → (hyperspace rewrite if enabled) → the XLA executor.
"""

from __future__ import annotations

import importlib
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple, Union as TUnion

from .config import Conf, HyperspaceConf
from .exceptions import HyperspaceException
from .execution.columnar import Table
from .plan import expr as E
from .plan.nodes import (Aggregate, Filter, Join, Limit, LogicalPlan, Project,
                         Scan, Sort, Union, Window)
from .schema import Schema
from .sources.interfaces import FileBasedSourceProviderManager
from .telemetry import span_names as SN
from .telemetry import trace as _trace


class Session:
    def __init__(self, conf: Optional[Dict[str, str]] = None,
                 system_path: Optional[str] = None):
        # Backend-aware persistent-cache setup (no-op after the first
        # session; initializes the jax backend, which callers that switch
        # platforms in-process have already pinned by now).
        from .execution import ensure_compilation_cache
        ensure_compilation_cache()
        self.conf = Conf(conf)
        if system_path is not None:
            from .index.constants import IndexConstants
            self.conf.set(IndexConstants.INDEX_SYSTEM_PATH, system_path)
        self.hs_conf = HyperspaceConf(self.conf)
        self._hyperspace_enabled = False
        self._event_logger = None
        # whyNot reasons of the most recent hyperspace rewrite pass —
        # PER THREAD (threading.local behind the property below): on the
        # multi-threaded serving path, one thread's optimize must not
        # clobber the collector another thread's workload capture is
        # about to attribute from.
        self._reason_tls = threading.local()
        from .config import CacheWithTransform
        self._provider_manager_cache = CacheWithTransform(
            self.hs_conf.file_based_source_builders, self._build_provider_manager)
        self._index_collection_manager = None
        # Serving layer: the result cache instance follows the serving
        # conf string (enabled flag + budgets) — rebuilt, and thereby
        # cleared, when that changes. The SQL plan memo keys on the
        # temp-view registry version (any view change flips it).
        self._result_cache_holder = CacheWithTransform(
            self.hs_conf.result_cache_conf_string, self._build_result_cache)
        # CacheWithTransform carries its own lock (config.py), but the
        # holder's build function touches session state: keep the outer
        # lock for the multi-threaded serving path's execute() probes.
        self._result_cache_lock = threading.Lock()
        # Temp views: eager dict + lock — registrations can race with
        # serving-path sql() lowering reading the registry version.
        self._temp_views: Dict[str, LogicalPlan] = {}
        self._views_lock = threading.Lock()
        self._temp_views_version = 0
        # Advisor state: the in-session workload log (advisor/workload.py
        # — created eagerly: a lazy check-then-create would race between
        # serving threads and lose records) and per-index applied counts
        # (rule_utils.log_index_usage increments under the lock;
        # statistics surface them).
        from .advisor.workload import WorkloadLog
        self._workload_log = WorkloadLog()
        self._index_usage_counts: Dict[str, int] = {}
        self._usage_counts_lock = threading.Lock()
        self._sql_plan_cache: "OrderedDict[Tuple, LogicalPlan]" = OrderedDict()
        self._sql_plan_stats = {"hits": 0, "misses": 0}
        # Cost-based optimizer state (optimizer/): the lazily-created
        # statistics provider (optimizer/stats.py attaches it on first
        # use), the chain records of the most recent join-reorder pass
        # (explain's "Join order:" section + bench's q-error read them),
        # and the observed output rows of recently executed inner joins
        # (executor-recorded; keyed by the composite join_actual_key —
        # condition repr + both side signatures — LRU-bounded).
        self._stats_provider = None
        self._last_join_order: Optional[list] = None
        self._join_actuals: "OrderedDict[str, int]" = OrderedDict()
        # The actuals dict is written by the executor on the
        # multi-threaded serving path (like _usage_counts, it needs its
        # own lock: unlocked LRU eviction could evict a key another
        # thread is about to move_to_end).
        self._join_actuals_lock = threading.Lock()
        # The memo is on the multi-threaded serving path (like the
        # result cache, which carries its own lock).
        self._sql_plan_lock = threading.Lock()
        # Span-tree trace of the most recent traced execution
        # (telemetry/trace.py; None until telemetry.trace.enabled runs a
        # query). Read by Hyperspace.last_trace() and explain's
        # "Trace:" section.
        self._last_trace = None
        # Artifact boot preload (r20, opt-in): warm the compiled-program
        # caches from the lake's AOT store, usage-ordered, within the
        # preload.maxMs/maxBytes budgets — so THIS process reaches its
        # first query with compile count ~ 0. Strictly best-effort: a
        # session must come up even with an unreadable artifact dir.
        if self.hs_conf.artifacts_preload_enabled():
            try:
                from .artifacts.manager import preload as _artifact_preload
                _artifact_preload(self)
            except Exception:
                pass

    # The reason collector of the calling thread's most recent rewrite
    # pass. Plain attribute syntax everywhere (apply_hyperspace writes,
    # why_not/capture read); the thread-local backing is invisible.
    @property
    def _last_reason_collector(self):
        return getattr(self._reason_tls, "collector", None)

    @_last_reason_collector.setter
    def _last_reason_collector(self, ctx) -> None:
        self._reason_tls.collector = ctx

    @property
    def index_collection_manager(self):
        """The per-session caching index manager (HyperspaceContext parity:
        rules and the user facade share one instance + one cache)."""
        if self._index_collection_manager is None:
            from .index.manager import CachingIndexCollectionManager
            self._index_collection_manager = CachingIndexCollectionManager(self)
        return self._index_collection_manager

    def _build_result_cache(self, raw: str):
        from .serving.result_cache import build_result_cache
        return build_result_cache(self)

    @property
    def result_cache(self):
        """The serving-layer result cache (serving/result_cache.py), or
        None while ``serving.result_cache.enabled`` is false."""
        with self._result_cache_lock:
            return self._result_cache_holder.load()

    @property
    def read(self) -> "DataFrameReader":
        # Fresh reader per access so option() calls don't leak across reads.
        return DataFrameReader(self)

    # ------------------------------------------------------------------
    # Temp views (parity: the reference's E2E suites query indexed data
    # through Spark views; view names are case-insensitive like Spark's).
    # ------------------------------------------------------------------

    def create_temp_view(self, name: str, df: "DataFrame",
                         replace: bool = False) -> None:
        key = name.lower()
        with self._views_lock:
            if key in self._temp_views and not replace:
                raise HyperspaceException(
                    f"Temp view already exists: {name}")
            self._temp_views[key] = df.plan
            self._temp_views_version += 1

    def table(self, name: str) -> "DataFrame":
        """DataFrame over a registered temp view. The view shares the
        underlying plan, so index rewrites (signatures are plan+file
        based) apply exactly as they do on the original DataFrame."""
        key = name.lower()
        with self._views_lock:
            plan = self._temp_views.get(key)
        if plan is None:
            raise HyperspaceException(f"No such temp view: {name}")
        return DataFrame(self, plan)

    def drop_temp_view(self, name: str) -> bool:
        with self._views_lock:
            dropped = self._temp_views.pop(name.lower(), None) is not None
            if dropped:
                self._temp_views_version += 1
        return dropped

    # ------------------------------------------------------------------
    # Source providers (parity: FileBasedSourceProviderManager.buildProviders).
    # ------------------------------------------------------------------

    @property
    def source_provider_manager(self) -> FileBasedSourceProviderManager:
        # Re-derived only when the conf string changes (CacheWithTransform).
        return self._provider_manager_cache.load()

    @staticmethod
    def _build_provider_manager(raw: str) -> FileBasedSourceProviderManager:
        providers = []
        for name in raw.split(","):
            name = name.strip()
            module_name, _, cls_name = name.rpartition(".")
            try:
                cls = getattr(importlib.import_module(module_name), cls_name)
            except (ImportError, AttributeError) as e:
                raise HyperspaceException(f"Cannot load source builder {name}") from e
            providers.append(cls())
        return FileBasedSourceProviderManager(providers)

    # ------------------------------------------------------------------
    # Hyperspace enable/disable (parity: package.scala:35-75).
    # ------------------------------------------------------------------

    def enable_hyperspace(self) -> "Session":
        self._hyperspace_enabled = True
        return self

    def disable_hyperspace(self) -> "Session":
        self._hyperspace_enabled = False
        return self

    def is_hyperspace_enabled(self) -> bool:
        return self._hyperspace_enabled

    # ------------------------------------------------------------------
    # Execution.
    # ------------------------------------------------------------------

    def optimize(self, plan: LogicalPlan,
                 _pre_normalized: bool = False,
                 diagnostic: bool = False) -> LogicalPlan:
        """General optimizations (column pruning), the hyperspace rewrite
        batch if enabled, then partition pruning. Partition pruning is
        always on (like Spark's native pruning) but must run AFTER the
        index rules: it narrows a Scan's file list, and the index rules
        fingerprint the relation's full file listing — pruning first would
        mismatch every index signature (same ordering rule as the
        data-skipping rule inside the batch).

        ``_pre_normalized``: the caller already ran serving.fingerprint.
        normalize (= the first two passes here) on ``plan`` — skip them
        rather than re-walking the tree (the result-cache miss path).

        ``diagnostic``: an inspection pass (explain) that will not
        execute the result — the rewrite runs with a silent collector,
        so it emits no usage telemetry, bumps no usageCount, and leaves
        the last real pass's whyNot reasons in place."""
        from .rules.column_pruning import prune_columns
        from .rules.pushdown import push_filters
        from .sources.partitions import prune_partitions
        # Catalyst-parity normalization first: predicates sink below
        # projections so the index rules see Scan→Filter shapes regardless
        # of how the user ordered select()/where().
        if not _pre_normalized:
            with _trace.span(SN.PLAN_NORMALIZE):
                plan = push_filters(plan)
                plan = prune_columns(plan)
        # Cost-based join reordering (optimizer/join_order.py) runs AFTER
        # normalization (it wants the pushed-down filters for selectivity)
        # and BEFORE the index rules, so FilterIndexRule/JoinIndexRule and
        # the advisor's what-if hooks match the reordered tree unchanged.
        # It is NOT part of serving.fingerprint.normalize: the result-cache
        # key's conf hash pins the reorder flag instead.
        if self.hs_conf.join_reorder_enabled():
            from .optimizer.join_order import reorder_joins
            plan = reorder_joins(self, plan, diagnostic=diagnostic)
        if self._hyperspace_enabled:
            from .rules.apply_hyperspace import apply_hyperspace
            ctx = None
            if diagnostic:
                from .rules.index_filters import ReasonCollector
                ctx = ReasonCollector(
                    self.hs_conf.filter_reason_enabled(), silent=True)
            plan = apply_hyperspace(self, plan, ctx)
        return prune_partitions(plan)

    def execute(self, plan: LogicalPlan, context=None):
        """Execute a plan under an explicit :class:`QueryContext`
        (serving/context.py). The context pins the per-query state that
        used to be implicit session attributes — result-cache handle,
        capture decision, io attribution — so the serving frontend can
        thread many concurrent queries (possibly sharing a process-wide
        cache) through shared worker threads. Callers that pass no
        context get a session-scoped one per call."""
        from .robustness import faults as _faults
        from .serving.context import QueryContext
        ctx = context if context is not None \
            else QueryContext.for_session(self)
        # The trace root (telemetry/trace.py): a no-op unless
        # telemetry.trace.enabled is set on this session or the serving
        # frontend handed the context a shared sweep trace; the opt-in
        # jax.profiler hook brackets the first query after arming. The
        # fault scope (robustness/faults.py) arms this session's
        # robustness.faults.* conf for exactly this execution — skipped
        # entirely (no contextvar write) while nothing is armed.
        with ctx.activate(), _faults.scope_for(self.hs_conf), \
                _trace.maybe_profile(self), _trace.query_trace(self, ctx):
            table = self._execute_observed(plan, ctx)
            if ctx.finish is None:
                return table
            # The answer's fetch to the host (DataFrame.to_arrow and
            # friends) runs inside the query's trace: ``query`` ends
            # after it.
            with _trace.span(SN.RESULT_FETCH):
                return ctx.finish(table)

    def _execute_observed(self, plan: LogicalPlan, ctx):
        t0 = time.perf_counter()
        error = False
        suppress = False
        try:
            if not ctx.capture:
                return self._execute_uncaptured(plan, ctx)
            # Advisor workload capture (advisor/workload.py): time
            # whatever path actually runs and record the canonical
            # plan + shapes + applied indexes. Resetting the reason
            # collector first makes ``applied`` attributable to THIS
            # execution (a result-cache hit runs no rewrite pass and
            # records an empty applied set).
            self._last_reason_collector = None
            table = self._execute_uncaptured(plan, ctx)
            from .advisor.workload import capture_execution
            capture_execution(self, plan, time.perf_counter() - t0)
            return table
        except BaseException as exc:
            error = True
            # A failed query is tail-keep-worthy by definition — and
            # this is where worker-thread failures (whose emit sites
            # never see the query's contextvars) surface on the query's
            # own context.
            _trace.keep_active("error")
            # A sweep-member failure the frontend's ladder will rescue
            # must not count as a completed failed query (the standalone
            # rerun records the real outcome); deadline cancellations
            # skip the rerun, so they stay.
            from .exceptions import QueryDeadlineError
            suppress = ctx.slo_suppress_error and \
                not isinstance(exc, QueryDeadlineError)
            raise
        finally:
            # SLO sensor feed (telemetry/slo.py): every query's (latency,
            # error, degraded) lands in the sliding window + the live
            # query-latency histogram — inside the trace scope, so a
            # breach event correlates with its query.
            if not suppress:
                from .telemetry import slo as _slo
                _slo.observe_query(
                    self, (time.perf_counter() - t0) * 1000.0,
                    error=error, degraded=ctx.degraded)

    def _execute_uncaptured(self, plan: LogicalPlan, ctx=None):
        if not self.hs_conf.adaptive_replan_enabled():
            return self._execute_once(plan, ctx)
        # Mid-query re-planning (adaptive/feedback.py): the staged
        # executor raises ReplanRequested at a join stage boundary whose
        # observed actual blew past its estimate. The observation
        # already landed in the correction store, so the re-optimize
        # pass below plans with the measured cardinality; the suppress
        # guard makes the retry run to completion (one re-plan per
        # query).
        from .adaptive import feedback as _feedback
        try:
            return self._execute_once(plan, ctx)
        except _feedback.ReplanRequested as rr:
            _feedback.emit_replan_event(self, rr)
            with _feedback.suppress_replans():
                return self._execute_once(plan, ctx)

    def _execute_once(self, plan: LogicalPlan, ctx=None):
        cache = ctx.result_cache if ctx is not None else self.result_cache
        if cache is not None:
            # Serving path: probe the result cache first — a hit skips
            # the rewrite batch AND execution (serving/result_cache.py);
            # a miss executes below and runs the admission policy. With
            # a frontend-owned context the cache may be the process-wide
            # CROSS-SESSION one — its keys pin plan, sources, index log
            # versions, and this session's conf hash, so sharing is safe
            # by construction.
            from .serving.result_cache import execute_with_cache
            return execute_with_cache(self, cache, plan)
        return self._run_optimized(self.optimize(plan))

    def _run_optimized(self, optimized: LogicalPlan):
        from .execution import execute as run
        trace_dir = self.hs_conf.trace_dir()
        if trace_dir:
            # XLA-profiler integration (SURVEY §5): device timelines for
            # every jitted program this execution launches, viewable in
            # TensorBoard / xprof.
            import jax

            with jax.profiler.trace(trace_dir):
                return run(optimized, session=self)
        return run(optimized, session=self)

    def create_dataframe(self, plan: LogicalPlan) -> "DataFrame":
        return DataFrame(self, plan)

    def sql(self, text: str) -> "DataFrame":
        """Lower one SQL SELECT over registered temp views onto the
        DataFrame IR (see hyperspace_tpu/sql.py for the supported
        subset); index rewrites apply exactly as for DataFrame queries.

        With the serving result cache enabled, the lowered plan is also
        memoized per (text, temp-view registry version, case mode) — the
        parse+analyze pass is pure given those, and a serving workload
        re-issues identical texts."""
        from .sql import sql as _sql
        size = self.hs_conf.result_cache_plan_cache_size() \
            if self.result_cache is not None else 0
        if size <= 0:
            return _sql(self, text)
        key = (text, self._temp_views_version,
               self.hs_conf.case_sensitive())
        with self._sql_plan_lock:
            plan = self._sql_plan_cache.get(key)
            if plan is not None:
                self._sql_plan_cache.move_to_end(key)
                self._sql_plan_stats["hits"] += 1
                return DataFrame(self, plan)
            self._sql_plan_stats["misses"] += 1
        df = _sql(self, text)
        with self._sql_plan_lock:
            self._sql_plan_cache[key] = df.plan
            while len(self._sql_plan_cache) > size:
                self._sql_plan_cache.popitem(last=False)
        return df


class DataFrameReader:
    def __init__(self, session: Session):
        self._session = session
        self._options: Dict[str, str] = {}

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key] = str(value)
        return self

    def parquet(self, *paths: str) -> "DataFrame":
        return self.format("parquet").load(*paths)

    def csv(self, *paths: str) -> "DataFrame":
        return self.format("csv").load(*paths)

    def json(self, *paths: str) -> "DataFrame":
        """Newline-delimited JSON files."""
        return self.format("json").load(*paths)

    def orc(self, *paths: str) -> "DataFrame":
        return self.format("orc").load(*paths)

    def text(self, *paths: str) -> "DataFrame":
        """One string column "value" per line (Spark text source)."""
        return self.format("text").load(*paths)

    def avro(self, *paths: str) -> "DataFrame":
        """Avro object container files (built-in reader, util/avro.py)."""
        return self.format("avro").load(*paths)

    def delta(self, path: str, version_as_of: Optional[int] = None
              ) -> "DataFrame":
        """Read a commit-log versioned table (lake/delta.py), optionally
        time-traveling to an older version."""
        reader = self.format("delta")
        if version_as_of is not None:
            reader._options["versionAsOf"] = str(version_as_of)
        return reader.load(path)

    def iceberg(self, path: str, snapshot_id: Optional[int] = None
                ) -> "DataFrame":
        """Read a snapshot/manifest versioned table (lake/iceberg.py)."""
        reader = self.format("iceberg")
        if snapshot_id is not None:
            reader._options["snapshotId"] = str(snapshot_id)
        return reader.load(path)

    def format(self, fmt: str) -> "_FormattedReader":
        return _FormattedReader(self._session, fmt, dict(self._options))


class _FormattedReader:
    def __init__(self, session: Session, fmt: str, options: Dict[str, str]):
        self._session = session
        self._fmt = fmt
        self._options = options

    def load(self, *paths: str) -> "DataFrame":
        relation = self._session.source_provider_manager.build_relation(
            list(paths), self._fmt, self._options)
        return DataFrame(self._session, Scan(relation))


class DataFrame:
    def __init__(self, session: Session, plan: LogicalPlan):
        self.session = session
        self.plan = plan

    # ------------------------------------------------------------------
    # Transformations.
    # ------------------------------------------------------------------

    @property
    def schema(self) -> Schema:
        return self.plan.schema

    @property
    def columns(self) -> List[str]:
        return self.plan.schema.names

    # Column names resolve case-insensitively against the schema (Spark
    # analyzer behavior; `hyperspace.caseSensitive=true` restores exact
    # matching). Unresolvable names pass through unchanged so the plan
    # constructors raise with the user's spelling.
    def _spelling(self, name: str, names: Optional[List[str]] = None) -> str:
        from .util.resolver import resolve
        avail = names if names is not None else self.plan.schema.names
        r = resolve(avail, name, self.session.hs_conf.case_sensitive())
        return r if r is not None else name

    def _resolve_expr(self, e: E.Expr,
                      names: Optional[List[str]] = None) -> E.Expr:
        return E.rename_columns(e, lambda n: self._spelling(n, names))

    def filter(self, condition: E.Expr) -> "DataFrame":
        return DataFrame(self.session,
                         Filter(self._resolve_expr(condition), self.plan))

    where = filter

    def select(self, *exprs: TUnion[str, E.Expr]) -> "DataFrame":
        flat: List[TUnion[str, E.Expr]] = []
        for e in exprs:
            if isinstance(e, (list, tuple)):
                flat.extend(e)
            else:
                flat.append(e)
        flat = [self._spelling(e) if isinstance(e, str)
                else self._resolve_expr(e) for e in flat]
        return DataFrame(self.session, Project(flat, self.plan))

    def join(self, other: "DataFrame", on: E.Expr, how: str = "inner") -> "DataFrame":
        both = list(self.plan.schema.names) + list(other.plan.schema.names)
        return DataFrame(self.session,
                         Join(self.plan, other.plan,
                              self._resolve_expr(on, both), how))

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        """Cartesian product (Spark's crossJoin). The SQL front-end emits
        this only for single-row sides (comma-joined global aggregates —
        the TPC-DS q28/q61/q88/q90 shape)."""
        return DataFrame(self.session,
                         Join(self.plan, other.plan, None, "cross"))

    crossJoin = cross_join

    def group_by(self, *cols: str) -> "GroupedData":
        return GroupedData(self, [self._spelling(c) for c in cols])

    groupBy = group_by

    def agg(self, *aggs: E.Expr) -> "DataFrame":
        return DataFrame(self.session,
                         Aggregate([], [self._resolve_expr(a) for a in aggs],
                                   self.plan))

    def sort(self, *orders) -> "DataFrame":
        normalized: List[Tuple[str, bool]] = []
        for o in orders:
            if isinstance(o, str):
                normalized.append((self._spelling(o), True))
            else:
                name, asc = o
                normalized.append((self._spelling(name), asc))
        return DataFrame(self.session, Sort(normalized, self.plan))

    order_by = sort
    orderBy = sort

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, Limit(n, self.plan))

    # ------------------------------------------------------------------
    # Actions.
    # ------------------------------------------------------------------

    def execute(self):
        """Run the (possibly rewritten) plan; returns the device Table."""
        return self.session.execute(self.plan)

    def optimized_plan(self) -> LogicalPlan:
        return self.session.optimize(self.plan)

    def _fetched(self, finish):
        """Run the plan and turn its device Table into ``finish(table)``
        inside the query's trace (the ``result.fetch`` span)."""
        from .serving.context import QueryContext
        ctx = QueryContext.for_session(self.session)
        ctx.finish = finish
        return self.session.execute(self.plan, ctx)

    def to_arrow(self):
        return self._fetched(Table.to_arrow)

    def to_pandas(self):
        return self._fetched(Table.to_pandas)

    def collect(self) -> List[tuple]:
        table = self.to_arrow()
        return [tuple(d.values()) for d in table.to_pylist()]

    def count(self) -> int:
        return self.execute().num_rows

    def explain(self, verbose: bool = False) -> str:
        text = self.plan.tree_string()
        if self.session.is_hyperspace_enabled():
            # Diagnostic pass: explaining a plan must not count as index
            # usage or emit usage telemetry.
            text += "\n\n== Optimized (hyperspace) ==\n" + \
                self.session.optimize(self.plan,
                                      diagnostic=True).tree_string()
        return text

    def with_column(self, name: str, expr: E.Expr) -> "DataFrame":
        """Add or replace a column (Spark's withColumn: the column to
        REPLACE matches case-insensitively, but the output keeps the
        caller's spelling — Spark emits col.as(the user's name))."""
        resolved = self._spelling(name)
        expr = self._resolve_expr(expr)
        exprs = [E.Col(n) if n != resolved else expr.alias(name)
                 for n in self.plan.schema.names]
        if resolved not in self.plan.schema.names:
            exprs.append(expr.alias(name))
        return DataFrame(self.session, Project(exprs, self.plan))

    withColumn = with_column

    def with_window(self, name: str, wexpr: E.Expr) -> "DataFrame":
        """Append an analytic (window) column — the analogue of Spark's
        ``withColumn(name, fn.over(windowSpec))``; build ``wexpr`` with
        ``hyperspace_tpu.functions.window(...)``. The reference inherits
        window execution from Spark SQL; here it is a first-class plan
        node (plan/nodes.py Window)."""
        if not isinstance(wexpr, E.WindowExpr):
            raise HyperspaceException(
                f"with_window expects a WindowExpr; got {wexpr!r}")
        return DataFrame(self.session,
                         Window([(name, self._resolve_expr(wexpr))],
                                self.plan))

    def drop(self, *names: str) -> "DataFrame":
        dropped = {self._spelling(n) for n in names}
        keep = [n for n in self.plan.schema.names if n not in dropped]
        if not keep:
            raise HyperspaceException("drop() would remove every column")
        return DataFrame(self.session, Project(keep, self.plan))

    def distinct(self) -> "DataFrame":
        """Distinct rows, lowered onto the grouped-aggregation machinery
        (group by every column) so it inherits the index rewrites and the
        SPMD path."""
        cols = list(self.plan.schema.names)
        # Collision-proof count alias: an agg whose name matches a group
        # column would overwrite it in the executor's output dict.
        cnt = "__distinct_cnt"
        while cnt in cols:
            cnt += "_"
        agg = Aggregate(cols, [E.Count(None).alias(cnt)], self.plan)
        return DataFrame(self.session, Project(cols, agg))

    def union(self, other: "DataFrame") -> "DataFrame":
        if self.plan.schema.names != other.plan.schema.names:
            raise HyperspaceException(
                f"union() column mismatch: {self.plan.schema.names} vs "
                f"{other.plan.schema.names}")
        mismatched = [
            (f.name, f.dtype, other.plan.schema.field(f.name).dtype)
            for f in self.plan.schema.fields
            if f.dtype != other.plan.schema.field(f.name).dtype]
        if mismatched:
            raise HyperspaceException(
                f"union() dtype mismatch: {mismatched}")
        return DataFrame(self.session, Union([self.plan, other.plan]))

    unionAll = union

    @property
    def write(self) -> "DataFrameWriter":
        """Write the (rewritten) query result to files — the output side
        of the user API (Spark's df.write analogue)."""
        return DataFrameWriter(self)


class DataFrameWriter:
    """Minimal writer: result → parquet/csv/json files. ``mode``:
    "error" (default, refuse to overwrite a non-empty dir) |
    "overwrite" | "append" (add a new part file).

    ``bucket_by(n, cols...)`` (parquet only) writes a bucketed, per-bucket-
    sorted dataset through the same hash→bucket→sort pipeline the index
    build uses — the analogue of the reference's ``saveWithBuckets``
    (util/DataFrameWriterExtensions.scala): bucket ids are recoverable
    from the file names and rows within each file are sorted by the
    bucketing columns."""

    def __init__(self, df: "DataFrame"):
        self._df = df
        self._mode = "error"
        self._bucket = None  # (num_buckets, [cols]) once bucket_by is set
        self._partition = None  # [cols] once partition_by is set

    def mode(self, mode: str) -> "DataFrameWriter":
        if mode not in ("error", "overwrite", "append"):
            raise HyperspaceException(f"Unknown write mode: {mode}")
        self._mode = mode
        return self

    def bucket_by(self, num_buckets: int, *cols: str) -> "DataFrameWriter":
        if num_buckets <= 0:
            raise HyperspaceException(
                f"bucket_by needs a positive bucket count, got {num_buckets}")
        if not cols:
            raise HyperspaceException(
                "bucket_by needs at least one bucketing column")
        cols = tuple(self._df._spelling(c) for c in cols)
        missing = [c for c in cols if c not in self._df.plan.schema]
        if missing:
            raise HyperspaceException(
                f"bucket_by columns not in the result: {missing}; "
                f"available: {self._df.plan.schema.names}")
        if self._partition is not None:
            raise HyperspaceException(
                "bucket_by and partition_by cannot be combined")
        self._bucket = (num_buckets, list(cols))
        return self

    bucketBy = bucket_by

    def partition_by(self, *cols: str) -> "DataFrameWriter":
        """Hive-partitioned layout (`col=value/` directories) — pairs with
        the reader's partition discovery/pruning (sources/partitions.py)."""
        if not cols:
            raise HyperspaceException(
                "partition_by needs at least one partition column")
        names = self._df.plan.schema.names
        cols = tuple(self._df._spelling(c) for c in cols)
        missing = [c for c in cols if c not in names]
        if missing:
            raise HyperspaceException(
                f"partition_by columns not in the result: {missing}; "
                f"available: {names}")
        if len(set(cols)) != len(cols):
            raise HyperspaceException(
                f"partition_by columns repeat: {list(cols)}")
        if len(set(cols)) == len(names):
            raise HyperspaceException(
                "partition_by cannot consume every output column")
        if self._bucket is not None:
            raise HyperspaceException(
                "bucket_by and partition_by cannot be combined")
        self._partition = list(cols)
        return self

    partitionBy = partition_by

    # Write protocol, in this order for every format:
    #   1. _check: cheap destination validation BEFORE the query runs
    #      (a refused write must not pay the plan's execution cost);
    #   2. materialize the result fully in memory;
    #   3. _finalize: only now delete (overwrite) + create the dir — so
    #      writing a query back over its own source is safe (the data was
    #      already read in step 2).

    def _check(self, path: str) -> None:
        if os.path.isfile(path):
            raise HyperspaceException(f"Path is a file, not a dir: {path}")
        if self._mode == "error" and os.path.isdir(path) and os.listdir(path):
            raise HyperspaceException(
                f"Path not empty: {path} (use mode('overwrite') or "
                "mode('append'))")

    def _prepare_dir(self, path: str) -> str:
        """Destination prep shared by all writers: delete (overwrite) and
        create the dir only AFTER the query result was materialized — so
        writing a query back over its own source is safe."""
        import shutil
        if self._mode == "overwrite" and os.path.isdir(path):
            shutil.rmtree(path)
        os.makedirs(path, exist_ok=True)
        return path

    def _finalize(self, path: str) -> str:
        import uuid
        self._prepare_dir(path)
        return os.path.join(path, f"part-{uuid.uuid4().hex[:12]}")

    BUCKET_SPEC_FILE = "_bucket_spec.json"  # invisible to readers (they
    #                                         list only format suffixes)

    def parquet(self, path: str) -> None:
        from .execution.columnar import write_parquet
        self._check(path)
        if self._bucket is not None:
            self._bucketed_parquet(path)
            return
        if self._partition is not None:
            self._guard_bucketed_dir(path)
            self._partitioned_parquet(path)
            return
        self._guard_bucketed_dir(path)
        table = self._df.execute().to_host()
        write_parquet(table, self._finalize(path) + ".parquet")

    def _partitioned_parquet(self, path: str) -> None:
        import uuid

        import pyarrow as pa
        import pyarrow.dataset as pa_ds

        at = self._df.to_arrow()  # materialize BEFORE destination prep
        part_schema = pa.schema([at.schema.field(c)
                                 for c in self._partition])
        self._prepare_dir(path)
        if at.num_rows == 0:
            # pa_ds.write_dataset emits NOTHING for 0 rows, leaving an
            # unreadable dir; a full-schema 0-row file keeps read-back
            # working (the bucketed writer does the same).
            import pyarrow.parquet as _pq
            if not any(f.endswith(".parquet")
                       for f in os.listdir(path)):
                _pq.write_table(
                    at, os.path.join(
                        path, f"part-{uuid.uuid4().hex[:12]}.parquet"))
            return
        pa_ds.write_dataset(
            at, path, format="parquet",
            partitioning=pa_ds.partitioning(part_schema, flavor="hive"),
            basename_template=(
                f"part-{uuid.uuid4().hex[:12]}-{{i}}.parquet"),
            existing_data_behavior="overwrite_or_ignore")

    def _bucketed_parquet(self, path: str) -> None:
        import json
        import uuid

        from .actions.create import _write_bucket_files
        from .ops import index_build

        num_buckets, cols = self._bucket
        spec_path = os.path.join(path, self.BUCKET_SPEC_FILE)
        if self._mode == "append" and os.path.isdir(path) and \
                os.listdir(path):
            # Appends must match the directory's existing bucket layout —
            # a different spec (or a previously unbucketed dir) would
            # silently put rows in files whose name promises a different
            # bucket (the recoverable-bucket-id contract).
            try:
                with open(spec_path) as f:
                    existing = json.load(f)
            except OSError:
                raise HyperspaceException(
                    f"Cannot bucket-append to {path}: it was not written "
                    "with bucket_by (no bucket spec found).") from None
            if existing != {"numBuckets": num_buckets, "columns": cols}:
                raise HyperspaceException(
                    f"bucket_by({num_buckets}, {cols}) does not match the "
                    f"existing layout of {path}: "
                    f"bucket_by({existing['numBuckets']}, "
                    f"{existing['columns']}).")
        table = self._df.execute()
        sorted_table, bounds = index_build.build_sorted_buckets(
            table, cols, num_buckets)
        host = sorted_table.to_host()
        self._prepare_dir(path)
        with open(spec_path, "w") as f:
            json.dump({"numBuckets": num_buckets, "columns": cols}, f)
        # A unique per-write suffix keeps Append-mode files from colliding;
        # the bucket id stays recoverable (bucket_id_from_file matches the
        # part-<id> prefix).
        suffix = uuid.uuid4().hex[:8]

        def name_for(bucket: int) -> str:
            return index_build.bucket_file_name(bucket).replace(
                ".parquet", f"-{suffix}.parquet")

        if host.num_rows == 0 and not any(
                f.endswith(".parquet") for f in os.listdir(path)):
            # Schema preservation for an empty result landing in an empty
            # dir: one 0-row file (read-back of a fileless dir would fail).
            from .execution.columnar import write_parquet
            write_parquet(host, os.path.join(path, name_for(0)))
            return
        _write_bucket_files(host, bounds, 0, num_buckets, path,
                            row_group_size=None, file_name=name_for)

    def csv(self, path: str) -> None:
        import pyarrow.csv as pa_csv
        self._check(path)
        self._reject_buckets("csv")
        at = self._df.to_arrow()
        pa_csv.write_csv(at, self._finalize(path) + ".csv")

    def _reject_buckets(self, fmt: str) -> None:
        if self._bucket is not None:
            raise HyperspaceException(
                f"bucket_by is only supported for parquet output, not {fmt}")
        if self._partition is not None:
            raise HyperspaceException(
                f"partition_by is only supported for parquet output, "
                f"not {fmt}")

    def _guard_bucketed_dir(self, path: str) -> None:
        """Non-bucketed writes must not land inside a bucketed dataset."""
        if self._mode == "append" and \
                os.path.isfile(os.path.join(path, self.BUCKET_SPEC_FILE)):
            raise HyperspaceException(
                f"{path} holds a bucketed dataset; appending "
                "non-bucketed rows would break its layout. Use "
                "bucket_by(<same spec>) or mode('overwrite').")

    def json(self, path: str) -> None:
        self._check(path)
        self._reject_buckets("json")
        df = self._df.to_pandas()
        df.to_json(self._finalize(path) + ".json",
                   orient="records", lines=True, date_format="iso")

    def avro(self, path: str) -> None:
        from .util.avro import write_avro
        self._check(path)
        self._reject_buckets("avro")
        at = self._df.to_arrow()
        write_avro(at, self._finalize(path) + ".avro")


class GroupedData:
    def __init__(self, df: DataFrame, group_cols: List[str]):
        self._df = df
        self._group_cols = group_cols

    def agg(self, *aggs: E.Expr) -> DataFrame:
        return DataFrame(self._df.session,
                         Aggregate(self._group_cols,
                                   [self._df._resolve_expr(a) for a in aggs],
                                   self._df.plan))

    def count(self) -> DataFrame:
        return self.agg(E.Count(None))
