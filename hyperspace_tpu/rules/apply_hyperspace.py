"""The hyperspace rewrite batch.

Parity reference: package.scala:35-46 — enableHyperspace injects the batch
``JoinIndexRule :: FilterIndexRule`` into the optimizer; ApplyHyperspace
(rules/ApplyHyperspace.scala:103) is the next-gen single entry point that
collects candidate indexes once per plan (CandidateIndexCollector) and picks
rewrites with ScoreBasedIndexPlanOptimizer. Both paths exist here: the
score-based optimizer is the default; the legacy ordered batch (join first —
it constrains both sides — then filter) is kept behind
``hyperspace.optimizer.scoreBased.enabled=false``.

Each pass records whyNot filter reasons into a ReasonCollector (enabled via
``hyperspace.index.filterReason.enabled``) that the session retains for the
``Hyperspace.why_not`` API.
"""

from __future__ import annotations

from typing import List

from ..index.constants import States
from ..index.log_entry import IndexLogEntry
from ..plan.nodes import IndexScan, LogicalPlan
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace
from .index_filters import CandidateIndexCollector, ReasonCollector


def active_indexes(session) -> List[IndexLogEntry]:
    """ACTIVE indexes from the session's shared caching index manager."""
    with _trace.span(SN.INDEX_LOOKUP):
        return session.index_collection_manager.get_indexes([States.ACTIVE])


def _applied_index_names(plan: LogicalPlan) -> List[str]:
    return [leaf.index_entry.name for leaf in plan.collect_leaves()
            if isinstance(leaf, IndexScan)]


def apply_hyperspace(session, plan: LogicalPlan,
                     ctx: ReasonCollector = None) -> LogicalPlan:
    with _trace.span(SN.INDEX_REWRITE) as sp:
        plan = _apply_hyperspace(session, plan, ctx)
        if sp is not None:
            sp.attrs["applied"] = len(_applied_index_names(plan))
        return plan


def _apply_hyperspace(session, plan: LogicalPlan,
                      ctx: ReasonCollector = None) -> LogicalPlan:
    from .data_skipping_rule import DataSkippingIndexRule
    from .filter_rule import FilterIndexRule
    from .join_rule import JoinIndexRule
    from .score_optimizer import ScoreBasedIndexPlanOptimizer

    if ctx is None:
        ctx = ReasonCollector(session.hs_conf.filter_reason_enabled())

    score_based = session.hs_conf.score_based_optimizer_enabled()
    if score_based:
        covering = [e for e in active_indexes(session)
                    if e.derivedDataset.kind == "CoveringIndex"]
        candidates = CandidateIndexCollector.collect(
            session, plan, covering, ctx)
        plan = ScoreBasedIndexPlanOptimizer().apply(
            session, plan, candidates, ctx)
    else:
        plan = JoinIndexRule().apply(session, plan, ctx)
        plan = FilterIndexRule().apply(session, plan, ctx)

    # ``applied`` reflects the final plan, not every rewrite the optimizer
    # scored along the way; the data-skipping rule appends its own names
    # below (it narrows Scan leaves in place rather than swapping them).
    ctx.applied = _applied_index_names(plan)
    if score_based and ctx.applied:
        from .rule_utils import log_index_usage
        log_index_usage(session, ctx, sorted(set(ctx.applied)),
                        plan.tree_string(), "Hyperspace indexes applied.")

    # Group-by indexes: unfiltered aggregations over remaining Scan leaves
    # probe a covering index whose bucket order lets the executor skip the
    # group-by sort (no reference analogue — see rules/groupby_rule.py).
    from .groupby_rule import GroupByIndexRule
    plan = GroupByIndexRule().apply(session, plan, ctx)
    ctx.applied = _applied_index_names(plan)

    # Data skipping last: it only narrows Scan leaves the covering rules
    # left in place (the covering rewrite is the better win when it applies).
    plan = DataSkippingIndexRule().apply(session, plan, ctx)

    if not ctx.silent:
        session._last_reason_collector = ctx
    return plan
