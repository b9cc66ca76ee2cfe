"""Shared rule machinery: candidate selection + plan transformation.

Parity reference: rules/RuleUtils.scala:52-569.

- ``get_candidate_indexes``: signature match in the common case; with Hybrid
  Scan enabled, file-overlap selection bounded by appended/deleted byte-ratio
  thresholds (RuleUtils.scala:52-190).
- ``transform_plan_to_use_index``: swap the source Scan for an IndexScan —
  index-only scan when the file sets match exactly, otherwise a Hybrid Scan
  (appended files merged in, deleted rows masked via the lineage column)
  (RuleUtils.scala:193-567). On TPU the BucketUnion of index + re-bucketed
  appended rows is a shard-aligned concatenation (SURVEY §5 item 3).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from ..index.constants import IndexConstants
from ..index.log_entry import FileInfo, IndexLogEntry
from ..index.signatures import LogicalPlanSignatureProvider
from ..plan import expr as E
from ..plan.nodes import Filter, IndexScan, LogicalPlan, Project, Scan
from ..schema import Schema
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace


def log_index_usage(session, ctx, index_names: List[str], plan_string: str,
                    message: str) -> None:
    """Emit an index-usage telemetry event unless this is a silent
    (diagnostic, e.g. why_not) pass — the single enforcement point of the
    'diagnostic passes emit no telemetry' invariant. The same point
    tallies per-index applied counts (session._index_usage_counts), which
    statistics/advisor surface to spot hot and dead indexes."""
    if ctx is not None and getattr(ctx, "silent", False):
        return
    with session._usage_counts_lock:
        counts = session._index_usage_counts
        for name in index_names:
            counts[name] = counts.get(name, 0) + 1
    from ..telemetry.events import HyperspaceIndexUsageEvent
    from ..telemetry.logging import get_logger
    get_logger(session.hs_conf.event_logger_class()).log_event(
        HyperspaceIndexUsageEvent(index_names=index_names,
                                  plan_string=plan_string, message=message))


def get_relation(session, plan: LogicalPlan):
    """The single supported file-based relation leaf of a linear plan, or
    None (parity: RuleUtils.getRelation — exactly one relation required)."""
    leaves = plan.collect_leaves()
    if len(leaves) != 1 or not isinstance(leaves[0], Scan):
        return None
    if not session.source_provider_manager.is_supported_relation(leaves[0]):
        return None
    return leaves[0].relation


def _plan_signature(entry: IndexLogEntry, scan: Scan) -> Optional[str]:
    """The scan's signature under the index's recorded provider: for
    file-based providers, a listing and stat of every source file."""
    recorded = entry.signature.signatures
    if not recorded:
        return None
    with _trace.span(SN.SOURCE_CHECK):
        provider = LogicalPlanSignatureProvider.create(recorded[0].provider)
        return provider.signature(scan)


def _current_file_infos(relation) -> List[FileInfo]:
    return [FileInfo(p, size, mtime, IndexConstants.UNKNOWN_FILE_ID)
            for p, size, mtime in relation.all_file_infos()]


def resolve_time_travel_entry(session, entry: IndexLogEntry, relation
                              ) -> IndexLogEntry:
    """For versioned sources (delta/iceberg analogues), swap the latest index
    entry for the log version built closest to the *scanned* table version
    (parity: DeltaLakeRelation.closestIndex:187 — time-travel-aware index
    selection). Non-versioned relations pass through unchanged."""
    closest_fn = getattr(relation, "closest_index_log_version", None)
    if closest_fn is None:
        return entry
    # History pairs are keyed by op-log id (entry.id), the version an
    # action's final commit was written at.
    target = closest_fn(entry.derivedDataset.properties)
    if target is None or target == entry.id:
        return entry
    from ..index.constants import States
    older = session.index_collection_manager.log_manager_for(
        entry.name).get_log(target)
    if older is not None and older.state == States.ACTIVE:
        return older
    return entry


def get_candidate_indexes(session, indexes: List[IndexLogEntry],
                          scan: Scan, ctx=None) -> List[IndexLogEntry]:
    """Indexes applicable to this scan. Signature equality, or — with Hybrid
    Scan on — bounded file-overlap. ``ctx`` (a ReasonCollector) records why
    stale indexes were dropped (parity: FileSignatureFilter,
    ApplyHyperspace.scala:54-67)."""
    hybrid = session.hs_conf.hybrid_scan_enabled()
    out = []
    for entry in indexes:
        entry = resolve_time_travel_entry(session, entry, scan.relation)
        if not hybrid:
            sig = _plan_signature(entry, scan)
            recorded = entry.signature.signatures[0].value \
                if entry.signature.signatures else None
            if sig is not None and recorded is not None and sig == recorded:
                out.append(entry)
            elif ctx is not None:
                ctx.add("SOURCE_DATA_CHANGED", entry,
                        "Source fingerprint mismatch (files were added, "
                        "removed, or modified since the index was built); "
                        "enable hybrid scan or refresh the index.")
            continue
        ok, appended, deleted = hybrid_scan_file_diff(
            session, entry, scan.relation)
        if ok:
            out.append(entry)
        elif ctx is not None:
            ctx.add("SOURCE_DATA_CHANGED", entry,
                    f"Hybrid Scan not applicable: {len(appended)} appended"
                    f" / {len(deleted)} deleted files exceed thresholds, "
                    "no common files, or deletes without lineage.")
    return out


def hybrid_scan_file_diff(session, entry: IndexLogEntry, relation
                          ) -> Tuple[bool, List[FileInfo], List[FileInfo]]:
    """(applicable?, appended files, deleted files) under Hybrid Scan rules
    (parity: RuleUtils.scala:96-160)."""
    current = set(_current_file_infos(relation))
    logged = entry.source_file_info_set
    common = current & logged
    if not common:
        return False, [], []
    appended = sorted(current - logged, key=lambda f: f.name)
    deleted = sorted(logged - common, key=lambda f: f.name)
    if deleted and not entry.has_lineage_column():
        return False, [], []
    common_bytes = sum(f.size for f in common)
    appended_bytes = sum(f.size for f in appended)
    deleted_bytes = sum(f.size for f in deleted)
    appended_ratio = appended_bytes / (appended_bytes + common_bytes) \
        if appended_bytes else 0.0
    deleted_ratio = deleted_bytes / (deleted_bytes + common_bytes) \
        if deleted_bytes else 0.0
    if appended_ratio > session.hs_conf.hybrid_scan_appended_ratio_threshold():
        return False, [], []
    if deleted_ratio > session.hs_conf.hybrid_scan_deleted_ratio_threshold():
        return False, [], []
    return True, appended, deleted


def common_source_bytes(entry: IndexLogEntry, relation) -> int:
    current = set(_current_file_infos(relation))
    return sum(f.size for f in (current & entry.source_file_info_set))


def index_scan_schema(entry: IndexLogEntry,
                      like: "Schema" = None) -> Schema:
    """The index schema exposed to the plan (lineage column hidden).

    ``like``: order columns as that schema does (the replaced Scan's) —
    the rewrite must not change the plan's output column ORDER, only its
    physical source (a select-free query returns relation-ordered columns
    either way; Spark keeps the original output attributes too)."""
    if like is not None:
        inner = set(entry.schema.names)
        ordered = [n for n in like.names if n in inner]
        ordered += [n for n in entry.schema.names if n not in set(ordered)]
        names = [n for n in ordered
                 if n != IndexConstants.DATA_FILE_NAME_ID]
        return entry.schema.select(names)
    names = [n for n in entry.schema.names
             if n != IndexConstants.DATA_FILE_NAME_ID]
    return entry.schema.select(names)


def transform_plan_to_use_index(session, entry: IndexLogEntry,
                                plan: LogicalPlan,
                                use_bucket_spec: bool) -> LogicalPlan:
    """Replace the plan's Scan leaf with an IndexScan over ``entry``.

    Exact-match source → index-only scan; otherwise Hybrid Scan state
    (appended file paths + deleted file ids) is attached to the IndexScan
    and realized by the executor (concat + lineage mask).
    """

    def replace(node: LogicalPlan) -> LogicalPlan:
        if isinstance(node, Scan):
            appended_paths: List[str] = []
            deleted_ids: List[int] = []
            if session.hs_conf.hybrid_scan_enabled():
                ok, appended, deleted = hybrid_scan_file_diff(
                    session, entry, node.relation)
                if ok:
                    appended_paths = [f.name for f in appended]
                    if deleted:
                        by_key = {(f.name, f.size, f.modifiedTime): f.id
                                  for f in entry.source_file_info_set}
                        deleted_ids = [
                            by_key[(f.name, f.size, f.modifiedTime)]
                            for f in deleted]
            return IndexScan(entry, index_scan_schema(entry, node.schema),
                             use_bucket_spec=use_bucket_spec,
                             deleted_file_ids=deleted_ids,
                             appended_files=appended_paths)
        return node

    return plan.transform_up(replace)


def is_plan_linear(plan: LogicalPlan) -> bool:
    """Scan/Filter/Project chain with single children all the way down
    (parity: JoinIndexRule.isPlanLinear)."""
    node = plan
    while True:
        if isinstance(node, Scan):
            return True
        if not isinstance(node, (Filter, Project)):
            return False
        children = node.children
        if len(children) != 1:
            return False
        node = children[0]


def _walk_base_references(plan: LogicalPlan):
    """(output name → base column map, all base columns the chain reads)
    for a linear Scan/Filter/Project chain, tracing Alias renames level by
    level so every node's references are translated through the mapping *at
    its depth*. Computed expressions map to None as outputs (not direct base
    attributes — parity with JoinIndexRule.scala:234; Spark gets this from
    exprIds) but their inputs still count toward the read set. Returns None
    for non-linear plans."""
    if isinstance(plan, Scan):
        return {n: n for n in plan.schema.names}, set()
    if isinstance(plan, Filter):
        walked = _walk_base_references(plan.child)
        if walked is None:
            return None
        mapping, refs = walked
        refs = set(refs)
        for r in plan.condition.references:
            base = mapping.get(r)
            if base is not None:
                refs.add(base)
        return mapping, refs
    if isinstance(plan, Project):
        walked = _walk_base_references(plan.child)
        if walked is None:
            return None
        mapping, refs = walked
        refs = set(refs)
        out = {}
        for e in plan.exprs:
            for r in e.references:
                base = mapping.get(r)
                if base is not None:
                    refs.add(base)
            inner = e.child if isinstance(e, E.Alias) else e
            out[e.name] = mapping.get(inner.column) \
                if isinstance(inner, E.Col) else None
        return out, refs
    return None


def output_to_base_mapping(plan: LogicalPlan) -> Optional[dict]:
    """Output column name → base relation column through a linear chain."""
    walked = _walk_base_references(plan)
    return None if walked is None else walked[0]


def collect_base_references(plan: LogicalPlan) -> Optional[set]:
    """Every base relation column a linear chain reads plus its direct base
    outputs — the coverage-check input, all in base namespace. None for
    non-linear plans."""
    walked = _walk_base_references(plan)
    if walked is None:
        return None
    mapping, refs = walked
    return refs | {b for b in mapping.values() if b is not None}


def collect_filter_project_columns(plan: LogicalPlan) -> Tuple[List[str], List[str]]:
    """(project/output columns, filter columns) referenced by a linear plan."""
    project_cols: List[str] = []
    filter_cols: List[str] = []
    node = plan
    saw_project = False
    while not isinstance(node, Scan):
        if isinstance(node, Project):
            if not saw_project:
                for e in node.exprs:
                    project_cols.extend(e.references)
                saw_project = True
        elif isinstance(node, Filter):
            filter_cols.extend(node.condition.references)
        node = node.children[0]
    if not saw_project:
        project_cols = list(plan.schema.names)
    return project_cols, filter_cols
