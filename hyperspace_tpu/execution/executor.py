"""Eager columnar plan executor.

Executes a logical plan bottom-up over device-resident tables. Each operator
is a fused XLA computation (jit happens inside the kernels); host↔device
traffic is limited to parquet IO, and the two architecturally-required scalar
syncs (join output size, group count) noted in ops/kernels.py.

The reference delegates all of this to Spark's execution engine; this module
is its TPU-native replacement (SURVEY §2 "the JVM/Spark execution engine
itself ... is the part the new framework replaces with XLA/Pallas kernels").
"""

from __future__ import annotations

import contextvars
import threading
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..exceptions import HyperspaceException, QueryDeadlineError
from ..ops import kernels
from ..plan import expr as E
from ..plan.nodes import (Aggregate, BucketUnion, Filter, IndexScan, Join, Limit,
                          LogicalPlan, Project, Scan, Sort, Union, Window)
from ..schema import BOOL, DATE, FLOAT64, INT32, INT64, STRING
from ..serving.context import check_deadline
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace
from . import shapes
from .columnar import (Column, Table, dictionaries_equal, filter_indices,
                       read_parquet, translate_codes)
from .evaluator import (eval_expr, eval_expr_maybe_fused,
                        eval_predicate_mask)
from .pushdown import prefers_pruned_read, pushable_filter


# Session for the in-flight execution: the SPMD dispatch reads its conf
# (distributed on/off) without threading a parameter through the recursion.
_SESSION: contextvars.ContextVar = contextvars.ContextVar(
    "hst_executing_session", default=None)


def execute(plan: LogicalPlan, session=None) -> Table:
    token = _SESSION.set(session)
    try:
        # Shape-class execution scope: kernels and the padded pipeline
        # below read the session's shapeBucketing conf through it. The
        # parallel-io scope routes every read under this execution through
        # the session's hyperspace.tpu.io.* conf (and its event logger).
        from ..parallel import io as pio
        conf = session.hs_conf if session is not None else None
        with shapes.use_conf(conf), pio.use_session(session), \
                shapes.compile_scope() as tally:
            # Row-returning distributed path: a {Filter, Project, Join}*
            # chain root (optionally under Sort/Limit) runs SPMD over the
            # mesh, rows gathered per device (execution/spmd.py). Aggregate
            # roots dispatch inside _execute; anything else falls through
            # to single-device. SPMD manages its own static shapes, so it
            # only ever sees compacted tables.
            from . import spmd
            result = _spmd_with_fault_fallback(
                lambda: spmd.try_execute_plan(plan, session,
                                              _execute_compact), session)
            if result is None:
                result = _execute(plan, needed=None)
                if result.is_padded:
                    # The result leaving the engine is always exact: class
                    # padding is an internal representation. Final results
                    # trim at the HOST boundary (one device_get, numpy
                    # slice): a device-side slice would compile one
                    # program per distinct row count — the literal-sweep
                    # serving pattern would recompile per query.
                    result = result.to_host()
        _emit_compile_event(session, tally["count"], tally["seconds"])
        return result
    finally:
        _SESSION.reset(token)


def _execute_compact(plan: LogicalPlan, needed: Optional[Set[str]]) -> Table:
    """_execute for callers outside the padded pipeline (SPMD leaf reads)."""
    return _execute(plan, needed).compact()


def _spmd_with_fault_fallback(run, session) -> Optional[Table]:
    """The SPMD -> single-device degradation ladder (robustness layer):
    a dispatch or compile FAULT — injected or real — degrades to
    single-device re-execution of the same stage instead of failing the
    query, observable as a DistributedFallbackEvent with reason
    "fault: ...". Structural mismatches already return None inside
    try_execute_* (the pre-existing fallback); a QueryDeadlineError is a
    CANCELLATION, never degraded; ``robustness.degrade.enabled=false``
    restores fail-loud behavior for debugging. The single-device rerun
    produces byte-identical answers (proven under fault injection in
    tests/test_robustness.py), because both paths execute the same
    logical stage."""
    try:
        return run()
    except QueryDeadlineError:
        raise
    except Exception as e:
        from ..adaptive.feedback import ReplanRequested
        if isinstance(e, ReplanRequested):
            # A re-plan request is a CONTROL transfer to
            # Session._execute_uncaptured, never a fault to degrade.
            raise
        if session is None or \
                not session.hs_conf.robustness_degrade_enabled():
            raise
        from ..robustness import faults as _faults
        from ..telemetry.logging import emit_distributed_fallback
        _faults.note(degraded_spmd=1)
        emit_distributed_fallback(
            session, "spmd_query", f"fault: {type(e).__name__}: {e}")
        return None


def _emit_compile_event(session, count: int, seconds: float) -> None:
    """Surface the per-execution XLA compile tally (shapes.py counter) as
    a KernelCompileEvent. No-op when nothing compiled or no session."""
    if session is None or count <= 0:
        return
    from ..telemetry.events import KernelCompileEvent
    from ..telemetry.logging import get_logger
    get_logger(session.hs_conf.event_logger_class()).log_event(
        KernelCompileEvent(
            message=f"{count} XLA compilation(s) during plan execution",
            count=count, seconds=round(seconds, 4),
            total=shapes.compile_count()))


def _shared_scan_key(plan: Scan, needed: Optional[Set[str]]):
    """Batch-sweep scan-sharing key: the full relation detail plus the
    column set about to be read (fingerprint._node_detail pins format,
    paths and options)."""
    from ..serving.fingerprint import _node_detail
    return (_node_detail(plan),
            tuple(sorted(needed)) if needed is not None else None)


def _execute(plan: LogicalPlan, needed: Optional[Set[str]]) -> Table:
    """Per-stage tracing wrapper: one ``exec.stage`` span per executed
    plan node, nesting with the recursion so the span tree mirrors the
    plan tree. ``idle()`` short-circuits the whole thing to a plain call
    while tracing is off (the no-op fast path contract).

    The per-node deadline check makes every stage boundary a
    cooperative cancellation point (robustness layer): deadline-less
    queries pay one contextvar read + one attribute test."""
    check_deadline("exec.stage")
    if isinstance(plan, (Filter, Project, Join)):
        # Whole-plan fusion (execution/fusion.py): a chain root opening a
        # fusible region executes as ONE banked program — no exec.stage
        # spans (and no host Tables) for its interior nodes. Aggregate
        # roots attempt fusion inside _execute_node, AFTER the SPMD
        # dispatch (the distributed tier keeps right of way; chains only
        # reach here once execute()'s spmd.try_execute_plan declined).
        from . import fusion
        fused = fusion.try_execute(plan, needed)
        if fused is not None:
            check_deadline("exec.stage")
            return fused
    if _trace.idle():
        table = _execute_node(plan, needed)
        # Checked on EXIT too: the recursion enters ancestors before
        # their slow leaves run, so entry checks alone would let an
        # expired query bubble all the way up uncancelled.
        check_deadline("exec.stage")
        return table
    with _trace.span(SN.EXEC_STAGE, node=plan.node_name) as sp:
        table = _execute_node(plan, needed)
        if sp is not None:
            sp.attrs["rows"] = int(table.num_rows)
        check_deadline("exec.stage")
        return table


def _execute_node(plan: LogicalPlan, needed: Optional[Set[str]]) -> Table:
    if isinstance(plan, Scan):
        from ..serving import batcher
        sweep = batcher.active_sweep()
        if sweep is not None:
            # Literal-sweep batch: every member reads the same sources —
            # the first member's table is reused by the rest.
            return sweep.shared_scan(
                _shared_scan_key(plan, needed),
                lambda: _execute_scan(plan, needed))
        return _execute_scan(plan, needed)
    if isinstance(plan, IndexScan):
        return _execute_index_scan(plan, needed)
    if isinstance(plan, Filter):
        child_needed = None if needed is None else \
            needed | set(plan.condition.references)
        if isinstance(plan.child, Scan):
            from ..serving import batcher
            sweep = batcher.active_sweep()
            if sweep is not None:
                # Under a sweep, row-group pushdown would prune
                # DIFFERENT row groups per member's literals; reading
                # the unpruned superset once is byte-identical (the full
                # predicate re-applies on device) and shares one table
                # across the batch. Sources past the chunk budget keep
                # the per-member streamed path (too big to pin).
                chunked = _chunked_filtered_scan(
                    plan.child, child_needed, plan.condition, None)
                if chunked is not None:
                    return chunked
                table = sweep.shared_scan(
                    _shared_scan_key(plan.child, child_needed),
                    lambda: _execute_scan(plan.child, child_needed))
                return _filter_table(table, plan.condition)
        if isinstance(plan.child, (Scan, IndexScan)):
            # Push row-group-prunable conjuncts into the parquet read. A
            # source scan's struct leaves aren't physical columns, so dotted
            # names can't be pushed there (index files store them flat).
            pa_filter = pushable_filter(
                plan.condition, plan.child.schema,
                allow_nested=isinstance(plan.child, IndexScan))
            if isinstance(plan.child, Scan):
                chunked = _chunked_filtered_scan(plan.child, child_needed,
                                                 plan.condition, pa_filter)
                if chunked is not None:
                    return chunked
                table = _execute_scan(plan.child, child_needed, pa_filter)
            else:
                buckets = _equality_bucket_subset(plan.child, plan.condition)
                chunked = _chunked_filtered_index_scan(
                    plan.child, child_needed, plan.condition, pa_filter,
                    bucket_subset=buckets)
                if chunked is not None:
                    return chunked
                pruned = pa_filter is not None and prefers_pruned_read(
                    plan.child.index_entry, plan.condition, plan.child.schema)
                table = _execute_index_scan(plan.child, child_needed, pa_filter,
                                            bucket_subset=buckets,
                                            prefer_pruned_read=pruned)
        else:
            table = _execute(plan.child, child_needed)
        return _filter_table(table, plan.condition)
    if isinstance(plan, Project):
        child_needed = set()
        for e in plan.exprs:
            child_needed.update(e.references)
        table = _execute(plan.child, child_needed)
        out = Table({e.name: eval_expr_maybe_fused(table, e)
                     for e in plan.exprs},
                    valid_rows=table.valid_rows)
        # Pass-through column projections keep the bucket-order invariant.
        bo = table.bucket_order
        if bo:
            name_map = {}
            for e in plan.exprs:
                inner = e.child if isinstance(e, E.Alias) else e
                if isinstance(inner, E.Col):
                    name_map.setdefault(inner.column, e.name)
            if all(k in name_map for k in bo[1]):
                out = Table(out.columns,
                            bucket_order=(bo[0], tuple(name_map[k] for k in bo[1])),
                            valid_rows=table.valid_rows)
        return out
    if isinstance(plan, Join):
        table = _execute_join(plan, needed)
        _record_join_actual(plan, table)
        return table
    if isinstance(plan, Aggregate):
        # Multi-device product path: run eligible aggregation subtrees SPMD
        # over the mesh (execution/spmd.py); fall back on any mismatch —
        # and, via the robustness ladder, on any dispatch/compile FAULT.
        from . import spmd
        spmd_result = _spmd_with_fault_fallback(
            lambda: spmd.try_execute_aggregate(plan, _SESSION.get(),
                                               _execute_compact),
            _SESSION.get())
        if spmd_result is not None:
            return spmd_result
        from . import fusion
        fused = fusion.try_execute(plan, needed)
        if fused is not None:
            return fused
        child_needed = set(plan.group_cols)
        for a in plan.aggs:
            child_needed.update(a.references)
        table = _execute(plan.child, child_needed)
        return _execute_aggregate(plan, table)
    if isinstance(plan, Window):
        out_names = {name for name, _ in plan.wexprs}
        child_needed = None if needed is None else \
            (needed - out_names) | {r for _, w in plan.wexprs
                                    for r in w.references}
        # Window internals (segmented scans, scatter-back through the sort
        # permutation) assume exact shapes; compact at the boundary.
        table = _execute(plan.child, child_needed).compact()
        return _execute_window(plan, table)
    if isinstance(plan, Sort):
        child_needed = None if needed is None else \
            needed | {c for c, _ in plan.orders}
        table = _execute(plan.child, child_needed)
        return _execute_sort(plan, table)
    if isinstance(plan, Limit):
        table = _execute(plan.child, needed)
        return table.slice(0, min(plan.n, table.num_rows))
    if isinstance(plan, (Union, BucketUnion)):
        # Align on the UNION's pruned output schema, not child 0's
        # materialized columns: a child whose own filter referenced extra
        # columns materializes a superset, and those extras differ per
        # child (found by the property oracle's generated union shapes).
        out_names = [n for n in plan.schema.names
                     if needed is None or n in needed]
        child_needed = needed
        if not out_names:
            # count(*) shape: no column is referenced — pick one and widen
            # the CHILD need-set so every child materializes it.
            out_names = plan.schema.names[:1]
            child_needed = None if needed is None else \
                needed | set(out_names)
        tables = [_execute(c, child_needed) for c in plan.children]
        aligned = [t.select(out_names) for t in tables]
        return Table.concat(aligned)
    raise HyperspaceException(f"Cannot execute plan node {plan.node_name}")


def _record_join_actual(plan: Join, table: Table) -> None:
    """Observed output cardinality of executed inner joins, kept on the
    session keyed by the composite join_actual_key (condition repr +
    both side signatures, LRU-bounded) so explain's "Join order:"
    section and bench's join_reorder phase can report estimated vs
    actual rows (q-error) for the cost-based reorderer's steps — and,
    with the adaptive loop on, so corrections never cross table pairs.

    This is also the mid-query re-plan trigger (adaptive/feedback.py):
    the staged executor owns stage boundaries, so after the write-back
    the adaptive layer may raise ReplanRequested here when the actual
    blew past the estimate — Session._execute_uncaptured catches it and
    re-optimizes with the fresh correction applied."""
    if plan.join_type != "inner" or plan.condition is None:
        return
    from ..serving import context as qctx
    key = qctx.join_actual_key(plan.condition, plan.left, plan.right)
    ctx = qctx.active_context()
    if ctx is not None:
        # Serving path: the QueryContext routes the write to its owning
        # session's locked store.
        session = ctx.session
        ctx.record_join_actual(key, int(table.num_rows))
    else:
        session = _SESSION.get()
        if session is None:
            return
        qctx.record_join_actual(session, key, int(table.num_rows))
    if session.hs_conf.adaptive_replan_enabled():
        from ..adaptive import feedback as _feedback
        _feedback.maybe_replan(session, key, int(table.num_rows))


def _filter_table(table: Table, condition) -> Table:
    """Filter operator body. The fused predicate program (one compile per
    predicate structure, literals as runtime args — evaluator.
    eval_predicate_mask_counted) covers the common shapes; everything
    else evaluates eagerly. Output rides the survivor count's length
    class either way (byte-identical after compaction)."""
    from .evaluator import eval_predicate_mask_counted
    fused = eval_predicate_mask_counted(table, condition)
    if fused is None:
        mask = eval_predicate_mask(table, condition)
        return table.filter(mask, padded=True)
    mask, m = fused
    cls = shapes.padded_length(m)
    idx = kernels.nonzero_pad_indices(mask, cls)
    out = table.take(idx, valid_rows=m if cls != m else None)
    # A subsequence of bucket-ordered rows is still bucket-ordered.
    return Table(out.columns, bucket_order=table.bucket_order,
                 valid_rows=out.valid_rows)


# Chunked-scan observability (mirrors ops.index_build.CHUNK_STATS): tests
# pin the scan-side device footprint with max_device_rows. Serving
# workers stream chunks concurrently, so every write goes through
# _note_chunk_scan under the lock — an unguarded max()+assign or += here
# loses updates under contention (HS301/HS302, scripts/analysis).
CHUNK_SCAN_STATS = {"max_device_rows": 0, "chunks": 0}
_CHUNK_STATS_LOCK = threading.Lock()


def _note_chunk_scan(rows: int) -> None:
    with _CHUNK_STATS_LOCK:
        CHUNK_SCAN_STATS["max_device_rows"] = max(
            CHUNK_SCAN_STATS["max_device_rows"], rows)
        CHUNK_SCAN_STATS["chunks"] += 1


def _chunked_filtered_scan(plan: Scan, needed: Optional[Set[str]],
                           condition, pa_filter=None) -> Optional[Table]:
    """Filter-over-scan for data larger than HBM: stream parquet chunks
    with row-group predicate pushdown, evaluate the full mask per chunk on
    device, and keep only survivors — the full dataset is never resident
    at once. Returns None when the source fits the chunk budget (the
    in-memory path is cheaper) or isn't chunkable (non-parquet, nested
    projection)."""
    import pyarrow.parquet as pq

    from ..index.constants import IndexConstants
    from .columnar import iter_dataset_chunks, parquet_row_counts

    session = _SESSION.get()
    chunk_rows = session.hs_conf.max_chunk_rows() if session is not None \
        else int(IndexConstants.TPU_MAX_CHUNK_ROWS_DEFAULT)
    relation = plan.relation
    fmt = getattr(relation, "data_file_format", relation.file_format)
    if fmt != "parquet":
        return None
    files = relation.all_files()
    if not files:
        return None
    cols = None
    if needed is not None:
        cols = [n for n in relation.schema.names if n in needed]
        if not cols:
            cols = [relation.schema.names[0]]
    # Hive partition columns live in directory names, not in the files —
    # the streaming reader can't attach them; read_relation_files can.
    part_names = {f.name for f in
                  getattr(relation, "partition_fields", lambda: [])()}
    if part_names and (cols is None or any(c in part_names for c in cols)):
        return None
    try:
        # Nested struct leaves carry dotted names that are NOT physical
        # top-level parquet columns — those go to the in-memory reader,
        # whose root-read+flatten path understands them.
        physical = set(pq.read_schema(files[0]).names)
        if cols is not None and any(c not in physical for c in cols):
            return None
        if sum(parquet_row_counts(files)) <= chunk_rows:
            return None
    except Exception:
        return None
    parts: List[Table] = []
    for chunk in iter_dataset_chunks(files, cols, chunk_rows, pa_filter):
        _note_chunk_scan(chunk.num_rows)
        mask = eval_predicate_mask(chunk, condition)
        parts.append(chunk.filter(mask))
    if not parts:
        from .columnar import empty_table
        return empty_table(relation.schema.select(cols)
                           if cols is not None else relation.schema)
    return Table.concat(parts)


def _execute_scan(plan: Scan, needed: Optional[Set[str]],
                  pa_filter=None) -> Table:
    relation = plan.relation
    cols = None
    if needed is not None:
        cols = [n for n in relation.schema.names if n in needed]
        if not cols:  # e.g. count(*) over no particular column.
            cols = [relation.schema.names[0]]
    files = relation.all_files()
    if not files:
        # A data-skipping rewrite can prune every file; the scan is empty.
        from .columnar import empty_table
        return empty_table(relation.schema.select(cols)
                           if cols is not None else relation.schema)
    fmt = getattr(relation, "data_file_format", relation.file_format)
    if fmt != "parquet":
        pa_filter = None
    from ..sources.partitions import read_relation_files
    from .columnar import pad_table_to_class
    # Class-pad at the scan boundary: every downstream chain (mask eval,
    # gathers, key hashing) then runs at the table's length class, and an
    # append/refresh that changes the row count lands on the same class
    # instead of recompiling the whole chain. Simple reads pad host-side
    # (free); partition-attach assemblies pad on device here.
    return pad_table_to_class(read_relation_files(
        relation, files, cols, fmt, filters=pa_filter, pad_to_class=True))


def _equality_bucket_subset(plan: IndexScan, condition) -> Optional[Set[int]]:
    """Bucket pruning: equality/IN predicates on the first indexed column pin
    the buckets a matching row can live in (the reference's
    INDEX_FILTER_RULE_USE_BUCKET_SPEC behavior — Spark prunes bucket files;
    we prune before IO)."""
    if not plan.use_bucket_spec:
        return None
    entry = plan.index_entry
    # The bucket id combines the hashes of ALL indexed columns (index_build.
    # bucket_ids_for), so pruning needs an equality constraint on every one.
    from .columnar import literal_to_device
    per_column_hashes = []
    for name in entry.indexed_columns:
        if name not in entry.schema:
            return None
        dtype = entry.schema.field(name).dtype
        values = None
        for conjunct in E.split_conjunctive_predicates(condition):
            vals = _equality_values(conjunct, name)
            if vals is not None:
                values = vals if values is None else (values & vals)
        if values is None or len(values) > 16:
            return None
        hashes = []
        for v in values:
            if dtype == STRING:
                hashes.append(kernels.hash32_value_host(str(v), dtype))
            else:
                hashes.append(kernels.hash32_value_host(
                    literal_to_device(v, dtype, None), dtype))
        per_column_hashes.append(hashes)

    combos = [None]
    for hashes in per_column_hashes:
        combos = [kernels.hash_combine_host(c, h) if c is not None else h
                  for c in combos for h in hashes]
        if len(combos) > 256:
            return None
    return {c % entry.num_buckets for c in combos}


def _equality_values(conjunct, column: str):
    if isinstance(conjunct, E.EqualTo):
        left, right = conjunct.left, conjunct.right
        if isinstance(left, E.Lit) and isinstance(right, E.Col):
            left, right = right, left
        if isinstance(left, E.Col) and left.column == column \
                and isinstance(right, E.Lit):
            return {right.value}
    if isinstance(conjunct, E.In) and isinstance(conjunct.value, E.Col) \
            and conjunct.value.column == column:
        if all(isinstance(o, E.Lit) for o in conjunct.options):
            return {o.value for o in conjunct.options}
    return None


def _index_scan_layout(plan: IndexScan, needed: Optional[Set[str]],
                       bucket_subset: Optional[Set[int]]):
    """File list (bucket-grouped order) + explicit read columns for an
    index scan. Returns (index_files, cols, buckets_have_single_file)."""
    from ..index.constants import IndexConstants
    from ..ops.index_build import bucket_id_from_file

    entry = plan.index_entry
    # Read index files grouped by bucket id: after an incremental refresh a
    # bucket's rows can span several version dirs, and bucket-grouped order
    # is what downstream bucket-aware operators expect.
    keyed = sorted(((bucket_id_from_file(f), f)
                    for f in entry.content.files),
                   key=lambda t: (t[0] is None, t[0] or 0, t[1]))
    index_files = [f for _, f in keyed]
    buckets_have_single_file = len({b for b, _ in keyed}) == len(keyed) \
        and all(b is not None for b, _ in keyed)
    if bucket_subset is not None:
        index_files = [f for b, f in keyed if b in bucket_subset]
    schema_names = entry.schema.names
    # Columns are ALWAYS explicit: index files live under "v__=<n>"
    # directories, and pyarrow's reader hive-infers a phantom "v__"
    # column from the path when asked for all columns (columns=None).
    if needed is not None:
        cols = [n for n in schema_names if n in needed]
        if not cols:
            cols = [schema_names[0]]
    else:
        cols = [n for n in plan.schema.names]
    if plan.deleted_file_ids and IndexConstants.DATA_FILE_NAME_ID not in cols:
        cols = cols + [IndexConstants.DATA_FILE_NAME_ID]
    return index_files, cols, buckets_have_single_file


def _chunked_filtered_index_scan(plan: IndexScan, needed: Optional[Set[str]],
                                 condition, pa_filter=None,
                                 bucket_subset: Optional[Set[int]] = None
                                 ) -> Optional[Table]:
    """Filter-over-index-scan for indexes larger than HBM: stream the
    bucket-ordered index files in chunks, evaluate the mask (and the
    hybrid deleted-row mask) per chunk, keep survivors. Survivors stay in
    bucket-grouped order, so the bucket_order invariant is preserved when
    there are no appended files. Returns None when the index fits the
    chunk budget (the in-memory/cached path is cheaper)."""
    from ..index.constants import IndexConstants
    from .columnar import (Table as T, empty_table, iter_dataset_chunks,
                           parquet_row_counts, read_parquet)

    session = _SESSION.get()
    chunk_rows = session.hs_conf.max_chunk_rows() if session is not None \
        else int(IndexConstants.TPU_MAX_CHUNK_ROWS_DEFAULT)
    entry = plan.index_entry
    index_files, cols, buckets_have_single_file = _index_scan_layout(
        plan, needed, bucket_subset)
    if not index_files:
        return None
    try:
        # Appended files count toward the footprint too (mirrors
        # spmd._leaf_within_budget, so a query the SPMD gate bounced here
        # is guaranteed to take THIS path, not full materialization).
        total = sum(parquet_row_counts(
            index_files + list(plan.appended_files)))
        if total <= chunk_rows:
            return None
    except Exception:
        return None
    lineage = IndexConstants.DATA_FILE_NAME_ID
    wanted = needed if needed is not None else set(plan.schema.names)
    out_cols = [c for c in cols if c != lineage or c in wanted]
    deleted = None
    if plan.deleted_file_ids:
        deleted = jnp.asarray(
            np.sort(np.asarray(plan.deleted_file_ids, dtype=np.int64)))
    parts: List[Table] = []
    app_parts: List[Table] = []
    for chunk in iter_dataset_chunks(index_files, cols, chunk_rows,
                                     pa_filter):
        _note_chunk_scan(chunk.num_rows)
        mask = eval_predicate_mask(chunk, condition)
        if deleted is not None:
            lc = chunk.column(lineage)
            mask = mask & ~kernels.isin_sorted(
                lc.data.astype(jnp.int64), deleted)
        parts.append(chunk.filter(mask))
    if plan.appended_files:
        # Appended files stream under the same budget — they can be a
        # sizable fraction of an over-HBM index (hybrid append ratio).
        # Dotted struct leaves aren't physical top-level columns in the
        # SOURCE files (the index stores them flat); those must go through
        # read_parquet's root-read+flatten path — per file, sliced to the
        # budget. EVERY file's schema is probed (appends can carry evolved
        # schemas), unreadable probes take the safe fallback.
        app_cols = [c for c in cols if c != lineage]
        import pyarrow.parquet as _pq

        from ..parallel import io as pio
        try:
            # Footer probes fan out over the reader pool (one metadata
            # round trip per appended file — on remote stores the latency
            # sum, not bandwidth, is what the pool hides). Lazy gather:
            # all() short-circuits at the first evolved schema, closing
            # the stream and cancelling not-yet-started probes.
            flat = all(
                not any(c not in names for c in app_cols)
                for names in pio.imap_ordered(
                    lambda f: set(_pq.read_schema(f).names),
                    list(plan.appended_files), label="schema_probe"))
        except Exception:
            flat = False
        if flat:
            app_iter = iter_dataset_chunks(list(plan.appended_files),
                                           app_cols, chunk_rows, None)
        else:
            def _app_chunks():
                # Host-side arrow read + flatten, sliced BEFORE device
                # conversion so HBM holds at most chunk_rows (the host
                # holds one source file's arrow — host RAM ≫ HBM). Only
                # the ROOT columns of the dotted leaves are read.
                import pyarrow as _pa
                for f in plan.appended_files:
                    top = set(_pq.read_schema(f).names)
                    roots = []
                    for c in app_cols:
                        root = c if c in top else c.split(".", 1)[0]
                        if root not in roots:
                            roots.append(root)
                    at = _pq.read_table(f, columns=roots)
                    while any(_pa.types.is_struct(fld.type)
                              for fld in at.schema):
                        at = at.flatten()
                    at = at.select(app_cols)
                    for lo in range(0, at.num_rows, chunk_rows):
                        yield Table.from_arrow(at.slice(lo, chunk_rows))
            from .columnar import _table_nbytes_estimate
            app_iter = pio.prefetch_iter(
                _app_chunks(), nbytes=_table_nbytes_estimate,
                label="hybrid_appended_chunks")
        for chunk in app_iter:
            _note_chunk_scan(chunk.num_rows)
            mask = eval_predicate_mask(chunk, condition)
            appended = chunk.filter(mask)
            if lineage in cols:
                fill = Column(INT64, jnp.full(
                    appended.num_rows, IndexConstants.UNKNOWN_FILE_ID,
                    jnp.int64))
                appended = appended.with_column(lineage, fill)
            app_parts.append(appended.select(cols))
    parts = [p for p in parts if p.num_rows > 0]
    app_parts = [p for p in app_parts if p.num_rows > 0]
    if not parts and not app_parts:
        return empty_table(entry.schema.select(out_cols))
    table = Table.concat(parts) if parts else \
        empty_table(entry.schema.select(cols))
    if entry.derivedDataset.kind == "CoveringIndex" \
            and buckets_have_single_file \
            and all(c in table.names for c in entry.indexed_columns):
        # Filtered subsequence of bucket-ordered rows is still bucket-
        # ordered (chunks stream files in bucket order; concat preserves).
        table = T(table.columns, bucket_order=(
            entry.num_buckets, tuple(entry.indexed_columns)))
    if app_parts:
        # Appended survivors merge into the bucket-ordered stream the
        # same way the in-memory path does (VERDICT r5 #9: beyond the
        # chunk budget the merge used to degrade to concat, costing the
        # downstream consumer the sort-free path exactly at the scales
        # that matter). Fallback stays the order-dropping concat.
        app_table = Table.concat(app_parts) if len(app_parts) > 1 \
            else app_parts[0]
        merged = _merge_appended_preserving_order(entry, table, app_table)
        if merged is not None:
            table = merged
        else:
            table = Table.concat([table, app_table]) if table.num_rows \
                else app_table
    if lineage in table.names and lineage not in wanted:
        table = table.select([n for n in table.names if n != lineage])
    return table


def _emit_index_cache_probe(index_name: str, hit: bool) -> None:
    """Surface IndexTableCache probes through telemetry (the hit/miss
    counters in execution/index_cache.py were previously counted but
    never reported anywhere). No-op outside a session context."""
    session = _SESSION.get()
    if session is None:
        return
    from ..telemetry.events import IndexCacheHitEvent, IndexCacheMissEvent
    from ..telemetry.logging import get_logger
    cls = IndexCacheHitEvent if hit else IndexCacheMissEvent
    get_logger(session.hs_conf.event_logger_class()).log_event(
        cls(message=f"index table cache {'hit' if hit else 'miss'}",
            index_name=index_name))


def _execute_index_scan(plan: IndexScan, needed: Optional[Set[str]],
                        pa_filter=None,
                        bucket_subset: Optional[Set[int]] = None,
                        prefer_pruned_read: bool = False) -> Table:
    from ..index.constants import IndexConstants

    entry = plan.index_entry
    index_files, cols, buckets_have_single_file = _index_scan_layout(
        plan, needed, bucket_subset)
    schema_names = entry.schema.names
    if not index_files and bucket_subset is not None \
            and not plan.appended_files:
        from .columnar import empty_table
        out_schema = plan.schema if needed is None else \
            plan.schema.select([n for n in plan.schema.names if n in needed]
                               or [plan.schema.names[0]])
        return empty_table(out_schema)
    if not index_files:
        from .columnar import empty_table
        table = empty_table(entry.schema.select(cols or entry.schema.names))
    else:
        from . import index_cache
        if index_cache.enabled() \
                and not (prefer_pruned_read and pa_filter is not None):
            # HBM-resident path: cache the *unfiltered* read (the Filter
            # node above always re-evaluates its mask on device, so skipping
            # the parquet-level pushdown is purely an IO trade). Leading-
            # indexed-column filters bypass the cache: the sorted layout
            # makes row-group pruning read ~selectivity of the file, far
            # cheaper than masking the whole cached table.
            key = (entry.id, entry.name, tuple(index_files),
                   tuple(cols) if cols is not None else None)
            cache = index_cache.get_cache()
            table = cache.get(key)
            _emit_index_cache_probe(entry.name, hit=table is not None)
            if table is None:
                # Padded host-side at read: the cache's only consumer is
                # this (padded-aware) scan path. pool=False: the cache
                # view admits under its own "index" namespace below —
                # routing the inner read through the scan namespace too
                # would double-store every index table.
                table = read_parquet(index_files, cols, pad_to_class=True,
                                     pool=False)
                cache.put(key, table)
        else:
            table = read_parquet(index_files, cols, filters=pa_filter,
                                 pad_to_class=True)
    if entry.derivedDataset.kind == "CoveringIndex" \
            and buckets_have_single_file \
            and all(c in table.names for c in entry.indexed_columns):
        # Physical layout invariant: files are read in bucket order and rows
        # are sorted by the indexed columns within each bucket. Downstream
        # joins exploit this to skip re-sorting. (Subsequent filters keep it.)
        table = Table(table.columns, bucket_order=(
            entry.num_buckets, tuple(entry.indexed_columns)),
            valid_rows=table.valid_rows)
    if plan.deleted_file_ids:
        lineage = table.column(IndexConstants.DATA_FILE_NAME_ID)
        deleted = jnp.asarray(
            np.sort(np.asarray(plan.deleted_file_ids, dtype=np.int64)))
        keep = ~kernels.isin_sorted(lineage.data.astype(jnp.int64), deleted)
        table = table.filter(keep, padded=True)
    if plan.appended_files:
        # The order-preserving merge scatters by absolute row position —
        # exact shapes (appends are the rare path; correctness first).
        table = table.compact()
        appended = read_parquet(
            plan.appended_files,
            [c for c in (cols or schema_names)
             if c != IndexConstants.DATA_FILE_NAME_ID])
        if IndexConstants.DATA_FILE_NAME_ID in (cols or schema_names) \
                and IndexConstants.DATA_FILE_NAME_ID not in appended.names:
            fill = Column(INT64, jnp.full(appended.num_rows,
                                          IndexConstants.UNKNOWN_FILE_ID, jnp.int64))
            appended = appended.with_column(IndexConstants.DATA_FILE_NAME_ID, fill)
        merged = _merge_appended_preserving_order(entry, table, appended)
        if merged is not None:
            table = merged
        else:
            table = Table.concat([table, appended.select(table.names)])
    wanted = needed if needed is not None else set(plan.schema.names)
    drop_lineage = (IndexConstants.DATA_FILE_NAME_ID in table.names
                    and IndexConstants.DATA_FILE_NAME_ID not in wanted)
    if drop_lineage:
        table = table.select([n for n in table.names
                              if n != IndexConstants.DATA_FILE_NAME_ID])
    from .columnar import pad_table_to_class
    return pad_table_to_class(table)


# Observability counters for the shuffle-free fast paths (tests assert the
# path is actually taken, mirroring the reference's plan-shape assertions in
# HybridScanSuite).
HYBRID_MERGE_COUNT = 0   # appended rows merged without dropping bucket order
FAST_JOIN_COUNT = 0      # joins that skipped the sort via bucket order


def _merge_appended_preserving_order(entry, table: Table,
                                     appended: Table) -> Optional[Table]:
    """Hybrid Scan without losing the merge join: re-bucket the appended
    rows on device, sort only them by (bucket, key), and two-way-merge them
    into the already-(bucket, key)-sorted index stream — so ``bucket_order``
    survives appends and the downstream join still skips its sort.

    The TPU analogue of the reference's query-time re-bucketing of appended
    data (RuleUtils.scala:509-567: RepartitionByExpression + BucketUnion
    keeps the zero-exchange SMJ); here the "shuffle" is one small sort of
    the appended rows and the union is a position-scatter merge.

    Returns None (caller falls back to order-dropping concat) unless the
    index stream carries bucket order on a single int-family key that fits
    int32 — the same constraints the fast-join consumer has.
    """
    global HYBRID_MERGE_COUNT
    from ..ops.index_build import bucket_ids_for

    if table.bucket_order is None or len(entry.indexed_columns) != 1:
        return None
    key = entry.indexed_columns[0]
    if key not in table.names or key not in appended.names:
        return None
    icol = table.column(key)
    if icol.dtype not in (INT32, INT64, DATE):
        return None
    if table.num_rows == 0 or appended.num_rows == 0:
        return None
    appended = appended.select(table.names)
    num_buckets = table.bucket_order[0]

    # int32-fit check for the (bucket << 32 | biased key) packing — one
    # fused reduction + host sync, mirroring _bucketed_merge_keys.
    acol = appended.column(key)
    to_check = [a for a in (icol.data, acol.data) if a.dtype == jnp.int64]
    if to_check:
        extreme = int(jnp.maximum(*[jnp.max(jnp.abs(a)) for a in to_check])
                      if len(to_check) == 2 else jnp.max(jnp.abs(to_check[0])))
        if extreme >= 2 ** 31 or extreme < 0:
            return None

    def composite(t: Table) -> jnp.ndarray:
        bids = bucket_ids_for(t, [key], num_buckets)
        return kernels.pack2_int32(bids, t.column(key).data.astype(jnp.int32))

    # Sort ONLY the appended rows; the index stream is already sorted.
    comp_a = composite(appended)
    perm_a = kernels.lex_sort_indices([comp_a])
    appended = appended.take(perm_a)
    comp_a = jnp.take(comp_a, perm_a)
    comp_i = composite(table)

    # Two-way merge positions (ties: index rows first).
    n_i, n_a = table.num_rows, appended.num_rows
    pos_i = jnp.arange(n_i, dtype=jnp.int32) + \
        jnp.searchsorted(comp_a, comp_i, side="left").astype(jnp.int32)
    pos_a = jnp.arange(n_a, dtype=jnp.int32) + \
        jnp.searchsorted(comp_i, comp_a, side="right").astype(jnp.int32)
    union = Table.concat([table, appended])  # unifies string dictionaries
    gather = jnp.zeros(n_i + n_a, jnp.int32) \
        .at[jnp.concatenate([pos_i, pos_a])] \
        .set(jnp.arange(n_i + n_a, dtype=jnp.int32))
    merged = union.take(gather)
    HYBRID_MERGE_COUNT += 1
    return Table(merged.columns, bucket_order=(num_buckets, (key,)))


# ---------------------------------------------------------------------------
# Join.
# ---------------------------------------------------------------------------

def _join_key_arrays(left: Table, right: Table,
                     pairs: List[Tuple[str, str]]):
    """Device key arrays for the join, in a shared comparable space."""
    if len(pairs) == 1:
        lname, rname = pairs[0]
        lc, rc = left.column(lname), right.column(rname)
        if lc.dtype == STRING or rc.dtype == STRING:
            if lc.dtype != rc.dtype:
                raise HyperspaceException("Join key type mismatch")
            return _string_join_keys(lc, rc)
        return lc.data, rc.data
    if len(pairs) == 2:
        lks, rks = [], []
        for lname, rname in pairs:
            lc, rc = left.column(lname), right.column(rname)
            if lc.dtype not in (INT32, DATE) or rc.dtype not in (INT32, DATE):
                break
            lks.append(lc.data)
            rks.append(rc.data)
        else:
            return (kernels.pack2_int32(lks[0], lks[1]),
                    kernels.pack2_int32(rks[0], rks[1]))
    # General N-key path, any key dtypes: dense-rank the union of key
    # tuples so both sides join on one int32 rank column (equal tuples ↔
    # equal ranks). One extra lex-sort over left+right keys, no host sync.
    n_left = left.num_rows
    union_keys = []
    for lname, rname in pairs:
        lc, rc = left.column(lname), right.column(rname)
        union_keys.append(_comparable_concat(lc, rc))
    ranks = kernels.dense_rank(union_keys)
    return ranks[:n_left], ranks[n_left:]


def _comparable_concat(lc: Column, rc: Column) -> jnp.ndarray:
    """Concatenated (left ++ right) key values in one comparable space."""
    if (lc.dtype == STRING) != (rc.dtype == STRING):
        raise HyperspaceException("Join key type mismatch")
    if lc.dtype == STRING:
        ldata, rdata = _string_join_keys(lc, rc)
        return jnp.concatenate([ldata, rdata])
    int_family = (INT32, INT64, DATE, BOOL)
    if lc.dtype in int_family and rc.dtype in int_family:
        return jnp.concatenate([lc.data.astype(jnp.int64),
                                rc.data.astype(jnp.int64)])
    if lc.dtype in (FLOAT64, "float32") and rc.dtype in (FLOAT64, "float32"):
        return jnp.concatenate([lc.data.astype(jnp.float64),
                                rc.data.astype(jnp.float64)])
    raise HyperspaceException(
        f"Join key type mismatch: {lc.dtype} vs {rc.dtype}")


def _string_join_keys(lc: Column, rc: Column):
    if dictionaries_equal(lc.dictionary, rc.dictionary):
        return lc.data, rc.data
    return lc.data, translate_codes(lc.dictionary, rc)


def _execute_join(plan: Join, needed: Optional[Set[str]]) -> Table:
    if plan.join_type == "cross":
        return _execute_cross_join(plan, needed)
    pairs = E.extract_equi_join_keys(plan.condition)
    if pairs is None:
        raise HyperspaceException(
            f"Only conjunctive equi-joins are supported; got {plan.condition!r}")
    left_names = set(plan.left.schema.names)
    right_names = set(plan.right.schema.names)
    # Normalize each pair to (left column, right column).
    norm: List[Tuple[str, str]] = []
    for a, b in pairs:
        if a in left_names and b in right_names:
            norm.append((a, b))
        elif b in left_names and a in right_names:
            norm.append((b, a))
        else:
            raise HyperspaceException(
                f"Join keys ({a}, {b}) do not split across the two sides")
    lneed = None if needed is None else \
        {n for n in needed if n in left_names} | {p[0] for p in norm}
    rneed = None if needed is None else \
        {n for n in needed if n in right_names} | {p[1] for p in norm}
    left = _execute(plan.left, lneed)
    right = _execute(plan.right, rneed)

    how = plan.join_type
    if how in ("semi", "anti"):
        # Membership probes sort/search raw key arrays — exact shapes.
        return _execute_semi_anti_join(left.compact(), right.compact(),
                                       norm, how)
    if how == "right":
        # right join = left join with the sides swapped: the output below
        # is assembled by column NAME against plan.schema, so the swap is
        # otherwise transparent.
        left, right = right, left
        norm = [(r, l) for l, r in norm]
        how = "left"
    if how in ("left", "full"):
        # Outer padding scatters by absolute row position — exact shapes.
        return _execute_outer_join(plan, left.compact(), right.compact(),
                                   norm, how)

    if not _padded_join_keys_ok(left, right, norm):
        # The general N-key path dense-ranks the concatenation of both
        # sides' keys — offsets are absolute row positions, so it needs
        # exact shapes.
        left, right = left.compact(), right.compact()
    lkeys, rkeys = _join_key_arrays(left, right, norm)
    # Inner join: drop null keys up front (pad rows ride along: the padded
    # filter keeps the key arrays and the table aligned).
    lvalid = _keys_validity(left, [p[0] for p in norm])
    if lvalid is not None:
        idx, m = filter_indices(lvalid, left.valid_rows)
        left = left.take(idx, valid_rows=m if int(idx.shape[0]) != m else None)
        lkeys = jnp.take(lkeys, idx, mode="clip")
    rvalid = _keys_validity(right, [p[1] for p in norm])
    if rvalid is not None:
        idx, m = filter_indices(rvalid, right.valid_rows)
        right = right.take(idx, valid_rows=m if int(idx.shape[0]) != m else None)
        rkeys = jnp.take(rkeys, idx, mode="clip")

    # Shuffle-free path: a side that carries the covering-index bucket order
    # on its join key is already sorted by (bucket, key) — probe it directly
    # instead of re-sorting (the TPU analogue of Spark consuming bucketSpec
    # for a zero-exchange sort-merge join, JoinIndexRule.scala:64-78).
    fast = _bucketed_merge_keys(left, right, norm, lkeys, rkeys)
    if fast is not None:
        global FAST_JOIN_COUNT
        FAST_JOIN_COUNT += 1
        lcomp, rcomp, swapped = fast
        if swapped:
            left, right = right, left
            lcomp, rcomp = rcomp, lcomp
        li, ri, total = kernels.merge_join_indices(
            lcomp, rcomp, left_valid=left.num_rows,
            right_valid=right.num_rows, padded_out=True)
        right_sorted = right
    else:
        r_padded = right.is_padded
        order = kernels.lex_sort_indices(
            [rkeys], valid_count=right.num_rows if r_padded else None,
            padded_out=r_padded)
        right_sorted = right.take(
            order, valid_rows=right.num_rows if r_padded else None)
        rkeys_sorted = jnp.take(rkeys, order, mode="clip")
        li, ri, total = kernels.merge_join_indices(
            lkeys, rkeys_sorted, left_valid=left.num_rows,
            right_valid=right.num_rows, padded_out=True)
    out_valid = total if int(li.shape[0]) != total else None
    out = {}
    taken_left = left.take(li, valid_rows=out_valid)
    taken_right = right_sorted.take(ri, valid_rows=out_valid)
    for n in plan.schema.names:
        # Children were column-pruned; emit only the materialized subset.
        if n in taken_left.columns:
            out[n] = taken_left.columns[n]
        elif n in taken_right.columns:
            out[n] = taken_right.columns[n]
    # The join output follows the probe (left) side's row order
    # (merge_join_indices emits ascending left indices), so the left
    # side's bucket order survives — downstream group-bys on those keys
    # can skip their sort.
    order_out = None
    lbo = left.bucket_order
    if lbo is not None and all(k in out for k in lbo[1]):
        order_out = lbo
    return Table(out, bucket_order=order_out, valid_rows=out_valid)


def _padded_join_keys_ok(left: Table, right: Table, norm) -> bool:
    """True when _join_key_arrays will take a per-row (elementwise or
    packed) key path that is safe over class-padded inputs. Mirrors its
    branching: 1 pair always; 2 pairs only when every key is INT32/DATE
    (otherwise it falls through to the absolute-offset dense-rank path)."""
    if len(norm) == 1:
        return True
    if len(norm) == 2:
        for lname, rname in norm:
            if left.column(lname).dtype not in (INT32, DATE) \
                    or right.column(rname).dtype not in (INT32, DATE):
                return False
        return True
    return False


def _execute_cross_join(plan: Join, needed: Optional[Set[str]]) -> Table:
    """Cartesian product via index expansion (left repeated, right tiled).
    The SQL front-end only emits this for single-row sides (comma-joined
    global aggregates — the TPC-DS q28/q61/q88/q90 shape), so the usual
    blow-up risk does not apply; a guard still bounds the general case."""
    left_names = set(plan.left.schema.names)
    lneed = None if needed is None else {n for n in needed
                                         if n in left_names}
    rneed = None if needed is None else {n for n in needed
                                         if n not in left_names}
    # Index expansion addresses absolute row positions — exact shapes.
    left = _execute(plan.left, lneed).compact()
    right = _execute(plan.right, rneed).compact()
    n, m = left.num_rows, right.num_rows
    if n * m > 50_000_000:
        raise HyperspaceException(
            f"Cross join too large: {n} x {m} rows")
    li = jnp.repeat(jnp.arange(n, dtype=jnp.int32), m)
    ri = jnp.tile(jnp.arange(m, dtype=jnp.int32), n)
    out = {}
    for name in plan.schema.names:
        if needed is not None and name not in needed:
            continue
        if name in left.columns:
            out[name] = left.column(name).take(li)
        elif name in right.columns:
            out[name] = right.column(name).take(ri)
    if not out:
        # count(*) over a cross join: materialize one column for the count.
        if left.columns:
            k = next(iter(left.columns))
            out[k] = left.columns[k].take(li)
        else:
            k = next(iter(right.columns))
            out[k] = right.columns[k].take(ri)
    return Table(out)


def _execute_semi_anti_join(left: Table, right: Table, norm,
                            how: str) -> Table:
    """Existence probe (SQL [NOT] IN / [NOT] EXISTS lowering): keep left
    rows with (semi) / without (anti) a key match on the right. No match
    expansion — membership is one sort + searchsorted, O(n log m). Null
    left keys never match (kept by anti, dropped by semi); null right keys
    are discarded up front. Left row order (and any bucket order) is
    preserved, the filter-like shape downstream rules rely on."""
    lkeys, rkeys = _join_key_arrays(left, right, norm)
    lvalid = _keys_validity(left, [p[0] for p in norm])
    rvalid = _keys_validity(right, [p[1] for p in norm])
    if rvalid is not None:
        rkeys = rkeys[rvalid]
    n_right = rkeys.shape[0]
    if n_right == 0:
        found = jnp.zeros(lkeys.shape[0], jnp.bool_)
    else:
        rsorted = jnp.sort(rkeys)
        pos = jnp.searchsorted(rsorted, lkeys)
        found = (pos < n_right) & (
            jnp.take(rsorted, jnp.minimum(pos, n_right - 1)) == lkeys)
    if lvalid is not None:
        found = found & lvalid
    mask = found if how == "semi" else ~found
    return left.filter(mask)


def _null_filled_like(table: Table, n: int) -> Dict[str, Column]:
    """n rows of every column of ``table``, all null (outer-join padding)."""
    out = {}
    for name, c in table.columns.items():
        data = jnp.zeros((n,) + c.data.shape[1:], c.data.dtype)
        out[name] = Column(c.dtype, data, jnp.zeros(n, jnp.bool_),
                          c.dictionary)
    return out


def _execute_outer_join(plan: Join, left: Table, right: Table, norm,
                        how: str) -> Table:
    """LEFT (or FULL) outer equi-join: inner matches plus unmatched
    preserved-side rows padded with nulls on the other side. Null join
    keys never match (SQL semantics) — those rows are emitted as
    unmatched. Row order: matched block first (probe order), then
    left-unmatched, then (full) right-unmatched; bucket order does not
    survive the concat."""
    lkeys_all, rkeys_all = _join_key_arrays(left, right, norm)
    lvalid = _keys_validity(left, [p[0] for p in norm])
    rvalid = _keys_validity(right, [p[1] for p in norm])
    l_idx = jnp.flatnonzero(lvalid) if lvalid is not None else None
    r_idx = jnp.flatnonzero(rvalid) if rvalid is not None else None
    lkeys = lkeys_all[l_idx] if l_idx is not None else lkeys_all
    rkeys = rkeys_all[r_idx] if r_idx is not None else rkeys_all

    order = kernels.lex_sort_indices([rkeys])
    rkeys_sorted = jnp.take(rkeys, order)
    li, ri, counts = kernels.merge_join_indices(lkeys, rkeys_sorted,
                                                return_counts=True)
    # Map subset indices back to original row positions.
    li_orig = jnp.take(l_idx, li) if l_idx is not None else li
    r_pos = jnp.take(r_idx, order) if r_idx is not None else order
    ri_orig = jnp.take(r_pos, ri)

    unmatched_l = jnp.flatnonzero(counts == 0)
    unmatched_l_orig = jnp.take(l_idx, unmatched_l) \
        if l_idx is not None else unmatched_l
    if lvalid is not None:
        unmatched_l_orig = jnp.concatenate(
            [unmatched_l_orig, jnp.flatnonzero(~lvalid)])

    blocks: List[Dict[str, Column]] = []
    matched_left = left.take(li_orig)
    matched_right = right.take(ri_orig)
    blocks.append({**matched_left.columns, **matched_right.columns})
    n_um_l = int(unmatched_l_orig.shape[0])  # HOST SYNC (scalar)
    if n_um_l:
        blocks.append({**left.take(unmatched_l_orig).columns,
                       **_null_filled_like(right, n_um_l)})
    if how == "full":
        # Right rows no left row matched: mark via a hit-scatter. ~hit
        # naturally includes null-key right rows (they never match).
        hit = jnp.zeros(right.num_rows, jnp.bool_).at[ri_orig].set(True)
        unmatched_r = jnp.flatnonzero(~hit)
        n_um_r = int(unmatched_r.shape[0])  # HOST SYNC (scalar)
        if n_um_r:
            blocks.append({**_null_filled_like(left, n_um_r),
                           **right.take(unmatched_r).columns})

    pieces = [Table({n: b[n] for n in b}) for b in blocks]
    ordered_names = [n for n in plan.schema.names
                     if n in pieces[0].names]
    out = Table.concat([p.select(ordered_names) for p in pieces])
    return out


def _bucketed_merge_keys(left: Table, right: Table, norm, lkeys, rkeys):
    """If one side is bucket-ordered on its single join key (covering-index
    layout), build composite (bucket, key) probe keys so the merge join can
    run without sorting that side. Returns (left_comp, right_comp, swapped)
    or None.

    Requires an integer-family key that fits in 32 bits (packed with the
    bucket id into one int64); the general path handles the rest.
    """
    if len(norm) != 1:
        return None
    lname, rname = norm[0]
    lcol, rcol = left.column(lname), right.column(rname)
    if lcol.dtype not in (INT32, INT64, DATE) or rcol.dtype != lcol.dtype:
        return None

    def ordered_on(table: Table, name: str):
        return table.bucket_order is not None and table.bucket_order[1] == (name,)

    if ordered_on(right, rname):
        swapped = False
        num_buckets = right.bucket_order[0]
    elif ordered_on(left, lname):
        swapped = True
        num_buckets = left.bucket_order[0]
    else:
        return None
    # Keys must fit int32 for the (bucket << 32 | biased key) packing; the
    # composite program also emits max(|key|) over the valid prefix, so
    # the check costs no extra program (pad tails are masked inside).
    lcomp, l_ext = kernels.bucket_composite_keys(
        lkeys, lcol.dtype, num_buckets, valid_count=left.num_rows)
    rcomp, r_ext = kernels.bucket_composite_keys(
        rkeys, rcol.dtype, num_buckets, valid_count=right.num_rows)
    for a, ext in ((lkeys, l_ext), (rkeys, r_ext)):
        if a.dtype == jnp.int64 and a.shape[0]:
            extreme = int(ext)  # HOST SYNC (single scalar)
            if extreme >= 2 ** 31 or extreme < 0:  # < 0: |int64 min| overflow
                return None
    return lcomp, rcomp, swapped


def _keys_validity(table: Table, names: Sequence[str]):
    v = None
    for n in names:
        c = table.column(n)
        cv = c.validity
        if c.dtype == STRING and cv is None:
            pass
        if cv is not None:
            v = cv if v is None else (v & cv)
    return v


# ---------------------------------------------------------------------------
# Aggregate / Sort.
# ---------------------------------------------------------------------------

def _null_aware_keys(c: Column) -> List[jnp.ndarray]:
    """Comparison keys for one column treating null as its own value that
    sorts before every real value: a (validity-flag, null-masked data) pair
    when nullable, just the data otherwise. The single encoding shared by
    sort, group-by, and the SPMD path's per-device order (spmd.py)."""
    if c.validity is None:
        return [c.data]
    return [c.validity.astype(jnp.int32),  # null(0) sorts first
            jnp.where(c.validity, c.data, jnp.zeros((), c.data.dtype))]


def _group_sort_keys(cols: Sequence[Column]) -> List[jnp.ndarray]:
    return [k for c in cols for k in _null_aware_keys(c)]


# Group-bys that avoided the full row sort (tests/bench assert the path is
# taken): SKIPPED = bucket order covers exactly the grouping keys (single
# pass, no sort at all); TWO_PHASE = bucket keys are a strict subset (runs
# aggregated then only the runs sorted).
GROUPBY_SORT_SKIPPED = 0
GROUPBY_TWO_PHASE = 0


def _execute_aggregate(plan: Aggregate, table: Table) -> Table:
    global GROUPBY_SORT_SKIPPED
    if not plan.group_cols:
        return _execute_global_aggregate(plan, table)
    key_cols = [table.column(g) for g in plan.group_cols]
    bo = table.bucket_order
    keys_non_null = all(c.validity is None for c in key_cols)
    padded_in = table.is_padded
    n_valid = table.num_rows
    if bo is not None and set(bo[1]) == set(plan.group_cols) \
            and keys_non_null:
        # Covering-index layout: rows sorted by (bucket, keys) ⇒ equal key
        # tuples are globally contiguous (a key tuple lives in exactly one
        # bucket), so segment detection works WITHOUT the O(n log n) sort —
        # the group-by analogue of the shuffle-free merge join. (Nullable
        # keys fall through: their fill values collide with real zeros.)
        sorted_table = table
        sorted_keys = [c.data for c in key_cols]
        GROUPBY_SORT_SKIPPED += 1
    elif bo is not None and set(bo[1]) < set(plan.group_cols) \
            and keys_non_null \
            and not any(isinstance(_unwrap_agg(a), E.CountDistinct)
                        for a in plan.aggs):
        # (CountDistinct is excluded: distinct counts of run partials
        # cannot be combined — the full-sort path below handles it.)
        # Bucket keys are a strict SUBSET of the grouping keys (e.g. Q3:
        # join output ordered by l_orderkey, grouped by (l_orderkey,
        # o_orderdate, o_shippriority)): equal group tuples need not be
        # globally contiguous, but RUNS of them are short-range — so run
        # the two-phase partial aggregation (segment per run, then sort
        # only the RUNS — usually ≈ the group count, vastly fewer than
        # rows — and combine). Sort cost drops from O(n log n) rows to
        # O(r log r) runs.
        global GROUPBY_TWO_PHASE
        GROUPBY_TWO_PHASE += 1
        return _execute_aggregate_two_phase(plan, table, key_cols)
    else:
        order = kernels.lex_sort_indices(
            _group_sort_keys(key_cols),
            valid_count=n_valid if padded_in else None,
            padded_out=padded_in)
        sorted_table = table.take(
            order, valid_rows=n_valid if padded_in else None)
        sorted_keys = _group_sort_keys(
            [sorted_table.column(g) for g in plan.group_cols])
    gids, num_groups = kernels.group_ids_from_sorted(
        sorted_keys, valid_count=n_valid if sorted_table.is_padded else None,
        padded_out=sorted_table.is_padded)
    if num_groups == 0:
        return Table({f.name: Column(f.dtype,
                                     jnp.zeros(0, _np_dtype_for(f.dtype)),
                                     None,
                                     _dict_for(table, f.name))
                      for f in plan.schema.fields})
    # The group count is data-dependent — outputs ride on its length class
    # (pad segments hold scatter identities, gathered with clip only).
    cap = shapes.padded_length(num_groups)
    out_valid = num_groups if cap != num_groups else None
    # One fused first-index + gather for every group column buffer.
    head_arrays, head_spec = [], []
    for g in plan.group_cols:
        c = sorted_table.column(g)
        head_arrays.append(c.data)
        head_spec.append((g, "d"))
        if c.validity is not None:
            head_arrays.append(c.validity)
            head_spec.append((g, "v"))
    heads = dict(zip(head_spec, kernels.segment_heads(
        gids, head_arrays, num_groups, padded_out=True)))
    out = {}
    for g in plan.group_cols:
        c = sorted_table.column(g)
        out[g] = Column(c.dtype, heads[(g, "d")], heads.get((g, "v")),
                        c.dictionary)
    for agg in plan.aggs:
        out[agg.name] = _eval_agg(agg, sorted_table, gids, num_groups,
                                  padded_out=True)
    return Table(out, valid_rows=out_valid)


def _execute_aggregate_two_phase(plan: Aggregate, table: Table,
                                 key_cols: List[Column]) -> Table:
    """Run-based partial aggregation: phase 1 segments CONSECUTIVE equal
    key tuples (no sort) and reduces each run to partials; phase 2 sorts
    only the runs and combines duplicate tuples. All on device; output is
    key-sorted like the main path.

    Shape classes: the run count and group count are both data-dependent,
    so phase-1 partials live on the run count's length class and the
    output on the group count's (kernels route pad rows to dropped
    segments; pad gathers clip)."""
    padded_in = table.is_padded
    n_valid = table.num_rows
    run_keys = [c.data for c in key_cols]
    rids, num_runs = kernels.group_ids_from_sorted(
        run_keys, valid_count=n_valid if padded_in else None,
        padded_out=padded_in)
    if num_runs == 0:
        return _execute_aggregate(
            plan, Table(dict(table.columns)))  # empty: reuse generic path
    cap_r = shapes.padded_length(num_runs)
    run_padded = cap_r != num_runs
    run_vals = list(kernels.segment_heads(rids, run_keys, num_runs,
                                          padded_out=True))

    order2 = kernels.lex_sort_indices(
        run_vals, valid_count=num_runs if run_padded else None,
        padded_out=run_padded)
    sorted_vals = list(kernels.gather_arrays(order2, run_vals))
    gids2, num_groups = kernels.group_ids_from_sorted(
        sorted_vals, valid_count=num_runs if run_padded else None,
        padded_out=run_padded)
    cap_g = shapes.padded_length(num_groups)
    out_valid = num_groups if cap_g != num_groups else None

    def combine(run_partial, op):
        # Fused gather-through-order2 + segment reduce.
        return kernels.gather_segment(run_partial, order2, gids2,
                                      num_groups, op, padded_out=True)

    out = {}
    group_vals = kernels.segment_heads(gids2, sorted_vals, num_groups,
                                       padded_out=True)
    for g, gv in zip(plan.group_cols, group_vals):
        src = table.column(g)
        out[g] = Column(src.dtype, gv, None, src.dictionary)
    for agg_expr in plan.aggs:
        agg = _unwrap_agg(agg_expr)
        name = agg_expr.name
        if isinstance(agg, E.Count):
            validity = None if agg.child is None \
                else eval_expr(table, agg.child).validity
            run_c = kernels.segment_count(rids, num_runs, validity,
                                          padded_out=True)
            out[name] = Column(INT64, combine(run_c, "sum"))
            continue
        child = _agg_child_column(agg, table)
        validity = child.validity
        out_validity = None
        total_valid = None
        if isinstance(agg, (E.Sum, E.Avg)):
            # Partial sums AND partial valid counts from one program.
            run_sums, run_valid = kernels.segment_agg(
                child.data, validity, rids, num_runs, "sum",
                padded_out=True)
            if run_valid is not None:
                total_valid = combine(run_valid, "sum")
                if validity is not None:
                    out_validity = total_valid > 0
            sums = combine(run_sums, "sum")
            if isinstance(agg, E.Sum):
                out[name] = Column(_sum_out_dtype(sums), sums, out_validity)
            else:
                if total_valid is None:
                    run_valid = kernels.segment_count(rids, num_runs,
                                                      padded_out=True)
                    total_valid = combine(run_valid, "sum")
                out[name] = Column(
                    FLOAT64,
                    sums.astype(jnp.float64) /
                    jnp.maximum(total_valid, 1).astype(jnp.float64),
                    out_validity)
        elif isinstance(agg, (E.Min, E.Max)):
            op = "min" if isinstance(agg, E.Min) else "max"
            run_m, run_valid = kernels.segment_agg(
                child.data, validity, rids, num_runs, op, widen=False,
                padded_out=True)
            if run_valid is not None:
                out_validity = combine(run_valid, "sum") > 0
            out[name] = Column(child.dtype, combine(run_m, op),
                               out_validity, child.dictionary)
        else:
            raise HyperspaceException(f"Unknown aggregate {agg!r}")
    return Table(out, valid_rows=out_valid)


def _np_dtype_for(dtype: str):
    return {INT32: jnp.int32, INT64: jnp.int64, "float32": jnp.float32,
            FLOAT64: jnp.float64, BOOL: jnp.bool_, DATE: jnp.int32,
            STRING: jnp.int32}[dtype]


def _dict_for(table: Table, name: str):
    if name in table.columns and table.columns[name].dtype == STRING:
        return table.columns[name].dictionary
    return None


def _unwrap_agg(agg: E.Expr) -> E.AggExpr:
    while isinstance(agg, E.Alias):
        agg = agg.child
    if not isinstance(agg, E.AggExpr):
        raise HyperspaceException(
            f"Aggregate list requires agg functions; got {agg!r}")
    return agg


def _agg_child_column(agg: E.AggExpr, table: Table) -> Column:
    child = eval_expr_maybe_fused(table, agg.child)
    if child.dtype == STRING and not isinstance(agg, (E.Min, E.Max)):
        raise HyperspaceException("sum/avg over string column")
    return child


def _acc_widen(values: jnp.ndarray, validity) -> jnp.ndarray:
    """Sum/avg accumulator: floats widen to f64, ints to i64; invalid
    rows contribute zero."""
    acc = values.astype(jnp.float64) \
        if jnp.issubdtype(values.dtype, jnp.floating) \
        else values.astype(jnp.int64)
    return acc if validity is None else jnp.where(validity, acc, 0)


def _sentinel_filled(child: Column, kind: str) -> jnp.ndarray:
    """Min/max input with invalid rows pushed past every real value."""
    if child.validity is None:
        return child.data
    sentinel = _max_sentinel(child.data.dtype) if kind == "min" \
        else _min_sentinel(child.data.dtype)
    return jnp.where(child.validity, child.data, sentinel)


def _sum_out_dtype(sums) -> str:
    return FLOAT64 if jnp.issubdtype(sums.dtype, jnp.floating) else INT64


def _count_distinct(child: Column, gids, num_groups: int) -> Column:
    """COUNT(DISTINCT value) per group: sort rows by (group, value), flag
    each (group, value) pair's first occurrence, segment-sum the flags.
    NULL values are excluded (SQL semantics) by parking their rows in a
    sentinel segment past the real groups."""
    n = child.data.shape[0]
    if n == 0:
        return Column(INT64, jnp.zeros(num_groups, jnp.int64))
    data = child.data.astype(jnp.int32) if child.dtype == BOOL else child.data
    gid_key = gids if child.validity is None else \
        jnp.where(child.validity, gids, num_groups)
    perm = kernels.lex_sort_indices([gid_key, data])
    sg = jnp.take(gid_key, perm)
    sv = jnp.take(data, perm)
    first = kernels.change_mask([sg, sv]).at[0].set(True)
    if jnp.issubdtype(sv.dtype, jnp.floating):
        # NaN != NaN would count every NaN separately; the sort places a
        # group's NaNs adjacent, so un-flag NaN-after-NaN pairs (Spark
        # semantics: NaN is ONE distinct value).
        nan_pair = jnp.concatenate([
            jnp.zeros(1, jnp.bool_),
            jnp.isnan(sv[1:]) & jnp.isnan(sv[:-1]) & (sg[1:] == sg[:-1])])
        first = first & ~nan_pair
    counts = kernels.segment_sum(first.astype(jnp.int64), sg,
                                 num_groups + 1)[:num_groups]
    return Column(INT64, counts)


def _eval_agg(agg: E.Expr, sorted_table: Table, gids, num_groups: int,
              padded_out: bool = False) -> Column:
    agg = _unwrap_agg(agg)
    if isinstance(agg, E.CountDistinct):
        col = _count_distinct(eval_expr(sorted_table, agg.child),
                              gids, num_groups)
        if padded_out:
            col = Column(col.dtype, shapes.pad_to(
                col.data, shapes.padded_length(num_groups)), col.validity,
                col.dictionary)
        return col
    if isinstance(agg, E.Count):
        if agg.child is None:
            data = kernels.segment_count(gids, num_groups,
                                         padded_out=padded_out)
        else:
            child = eval_expr(sorted_table, agg.child)
            data = kernels.segment_count(gids, num_groups, child.validity,
                                         padded_out=padded_out)
        return Column(INT64, data)
    child = _agg_child_column(agg, sorted_table)
    validity = child.validity
    if isinstance(agg, (E.Sum, E.Avg)):
        op = "mean" if isinstance(agg, E.Avg) else "sum"
        value, counts = kernels.segment_agg(child.data, validity, gids,
                                            num_groups, op,
                                            padded_out=padded_out)
        # SQL semantics: a group with no valid values aggregates to NULL.
        out_validity = (counts > 0) if validity is not None else None
        if isinstance(agg, E.Sum):
            return Column(_sum_out_dtype(value), value, out_validity)
        return Column(FLOAT64, value, out_validity)
    if isinstance(agg, (E.Min, E.Max)):
        op = "min" if isinstance(agg, E.Min) else "max"
        value, counts = kernels.segment_agg(child.data, validity, gids,
                                            num_groups, op, widen=False,
                                            padded_out=padded_out)
        out_validity = (counts > 0) if validity is not None else None
        return Column(child.dtype, value, out_validity, child.dictionary)
    raise HyperspaceException(f"Unknown aggregate {agg!r}")


def _max_sentinel(dtype):
    return jnp.array(jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating)
                     else jnp.iinfo(dtype).max, dtype)


def _min_sentinel(dtype):
    return jnp.array(jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating)
                     else jnp.iinfo(dtype).min, dtype)


def _execute_global_aggregate(plan: Aggregate, table: Table) -> Table:
    if table.is_padded:
        # One fused program: pad rows scatter to a dropped segment.
        gids = kernels.global_segment_ids(table.num_rows, table.data_rows)
    else:
        gids = jnp.zeros(table.data_rows, jnp.int32)
    out = {}
    for agg in plan.aggs:
        out[agg.name] = _eval_agg(agg, table, gids, 1)
    return Table(out)


def _segmented_scan(data: jnp.ndarray, seg_start: jnp.ndarray, op):
    """Inclusive running ``op`` within segments of pre-sorted rows:
    ``seg_start`` marks each segment's first row. One associative_scan —
    the XLA-native way to reset an accumulator at segment boundaries
    (no data-dependent Python control flow)."""
    def combine(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, op(av, bv))

    _, out = jax.lax.associative_scan(combine, (seg_start, data))
    return out


def _execute_window(plan: Window, table: Table) -> Table:
    """Analytic functions as sort + segmented scans, preserving the
    child's row order (outputs are computed in partition-sorted space and
    scattered back through the sort permutation). Window exprs sharing a
    (partition, order) spec share one sort."""
    n = table.num_rows
    out = dict(table.columns)
    if n == 0:
        for name, w in plan.wexprs:
            f = plan.schema.field(name)
            dic = _dict_for(table, w.arg.column) if (
                w.arg is not None and f.dtype == STRING) else None
            out[name] = Column(f.dtype, jnp.zeros(0, _np_dtype_for(f.dtype)),
                               None, dic)
        return Table(out, bucket_order=table.bucket_order)
    iota = jnp.arange(n, dtype=jnp.int32)

    specs = {}
    for name, w in plan.wexprs:
        key = (tuple(p.column for p in w.partition),
               tuple((o.column, asc) for o, asc in w.orders))
        specs.setdefault(key, []).append((name, w))

    for (pcols, oitems), group in specs.items():
        keys, asc_flags = [], []
        for p in pcols:
            for k in _null_aware_keys(table.column(p)):
                keys.append(k)
                asc_flags.append(True)
        for oc, a in oitems:
            for k in _null_aware_keys(table.column(oc)):
                keys.append(k)
                asc_flags.append(a)
        order = kernels.lex_sort_indices(keys, asc_flags) if keys else iota
        if pcols:
            pkeys_sorted = _group_sort_keys(
                [table.column(p).take(order) for p in pcols])
            pids, n_part = kernels.group_ids_from_sorted(pkeys_sorted)
        else:
            pkeys_sorted = []
            pids, n_part = jnp.zeros(n, jnp.int32), 1
        part_start = jnp.concatenate(
            [jnp.ones(1, jnp.bool_), pids[1:] != pids[:-1]])
        part_first = kernels.segment_first_index(pids, n_part)
        pos = iota - jnp.take(part_first, pids)
        peer_gid = peer_first = peer_last = None
        if oitems:
            okeys_sorted = _group_sort_keys(
                [table.column(oc).take(order) for oc, _ in oitems])
            peer_gid, n_peer = kernels.group_ids_from_sorted(
                pkeys_sorted + okeys_sorted)
            peer_first = kernels.segment_first_index(peer_gid, n_peer)
            peer_last = kernels.segment_max(iota, peer_gid, n_peer)

        for name, w in group:
            dtype = plan.schema.field(name).dtype
            validity_s = None
            dic = None
            if w.fn == "row_number":
                vals = (pos + 1).astype(jnp.int64)
            elif w.fn == "rank":
                vals = (jnp.take(peer_first, peer_gid)
                        - jnp.take(part_first, pids) + 1).astype(jnp.int64)
            elif w.fn == "dense_rank":
                first_peer = jnp.take(peer_gid, jnp.take(part_first, pids))
                vals = (peer_gid - first_peer + 1).astype(jnp.int64)
            else:
                arg = None if w.arg is None \
                    else table.column(w.arg.column).take(order)
                vals, validity_s = _window_agg(
                    w, arg, pids, n_part, part_start, peer_gid, peer_last)
                if dtype == STRING:
                    dic = arg.dictionary
            data = jnp.zeros(n, vals.dtype).at[order].set(vals)
            validity = None if validity_s is None else \
                jnp.zeros(n, jnp.bool_).at[order].set(validity_s)
            out[name] = Column(dtype, data, validity, dic)
    return Table(out, bucket_order=table.bucket_order)


def _window_agg(w: E.WindowExpr, arg: Optional[Column], pids, n_part,
                part_start, peer_gid, peer_last):
    """One windowed aggregate in partition-sorted space. Returns (values,
    validity or None). Frames: 'partition' = whole partition;
    'rows' = running; 'range' = running where order-key peers share the
    value of their last row (the SQL default frame with ORDER BY)."""
    fn = w.fn
    frame = w.frame
    if frame == "range" and peer_gid is None:
        frame = "partition"  # no ORDER BY: every row is a peer

    if fn == "count":
        data = jnp.ones(pids.shape[0], jnp.int64) if arg is None or \
            arg.validity is None else arg.validity.astype(jnp.int64)
    elif fn in ("sum", "avg"):
        if arg.dtype == STRING:
            # Same guard as the aggregate path (_agg_child_column):
            # summing dictionary codes would be silently wrong.
            raise HyperspaceException(f"{fn} over string column")
        data = _acc_widen(arg.data, arg.validity)
        if fn == "avg":
            data = data.astype(jnp.float64)
    else:  # min / max
        data = _sentinel_filled(arg, fn)

    valid = None if arg is None or arg.validity is None \
        else arg.validity.astype(jnp.int64)

    def framed(values, op, identity_op_name):
        if frame == "partition":
            seg = {"sum": kernels.segment_sum,
                   "min": kernels.segment_min,
                   "max": kernels.segment_max}[identity_op_name](
                values, pids, n_part)
            return jnp.take(seg, pids)
        running = _segmented_scan(values, part_start, op)
        if frame == "range":
            running = jnp.take(running, jnp.take(peer_last, peer_gid))
        return running

    if fn == "count":
        return framed(data, jnp.add, "sum"), None
    if fn in ("min", "max"):
        op = jnp.minimum if fn == "min" else jnp.maximum
        vals = framed(data, op, fn)
        if valid is None:
            return vals, None
        cnt = framed(valid, jnp.add, "sum")
        return vals, cnt > 0
    # sum / avg
    total = framed(data, jnp.add, "sum")
    if fn == "avg":
        cnt = framed(valid if valid is not None
                     else jnp.ones(pids.shape[0], jnp.int64),
                     jnp.add, "sum")
        vals = total / jnp.maximum(cnt, 1)
        return vals, (cnt > 0) if valid is not None else None
    if valid is None:
        return total, None
    cnt = framed(valid, jnp.add, "sum")
    return total, cnt > 0


def _execute_sort(plan: Sort, table: Table) -> Table:
    keys, ascending = [], []
    for name, asc in plan.orders:
        # SQL order-by null placement (Spark default): NULLS FIRST when
        # ascending, NULLS LAST when descending — sorting the null-aware
        # (flag, data) keys in the requested direction realizes both.
        for k in _null_aware_keys(table.column(name)):
            keys.append(k)
            ascending.append(asc)
    padded = table.is_padded
    order = kernels.lex_sort_indices(
        keys, ascending, valid_count=table.num_rows if padded else None,
        padded_out=padded)
    return table.take(order,
                      valid_rows=table.num_rows if padded else None)
