"""DataSkippingIndexRule: prune a scan's file list using per-file sketches.

No parity in the mounted reference snapshot (DataSkippingIndex landed in
later Hyperspace versions — SURVEY.md version note); behaviorally this is
the later reference's ApplyDataSkippingIndex: the source relation is kept,
but its file listing is narrowed to the files whose sketches cannot refute
the filter predicate. Covering-index rules run first; this rule only touches
Scan leaves they left in place.

Sketch probing is host-side numpy over the (one row per file) sketch table;
unknown predicate shapes conservatively keep all files.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

import numpy as np
import pyarrow.parquet as pq

from ..index.log_entry import IndexLogEntry, Sketch
from ..plan import expr as E
from ..plan.nodes import Filter, LogicalPlan, Scan
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace
from .rule_utils import _plan_signature, get_relation


class DataSkippingIndexRule:
    name = "DataSkippingIndexRule"

    def apply(self, session, plan: LogicalPlan, ctx=None) -> LogicalPlan:
        from .apply_hyperspace import active_indexes
        candidates = [e for e in active_indexes(session)
                      if e.derivedDataset.kind == "DataSkippingIndex"]
        if not candidates:
            return plan

        applied: List[str] = []

        def rewrite(node: LogicalPlan) -> LogicalPlan:
            if isinstance(node, Filter) and isinstance(node.child, Scan):
                pruned = self._try_prune(session, node.child, node.condition,
                                         candidates, applied, ctx)
                if pruned is not None:
                    return Filter(node.condition, pruned)
            return node

        new_plan = plan.transform_up(rewrite)
        if applied:
            from .rule_utils import log_index_usage
            log_index_usage(session, ctx, sorted(set(applied)),
                            new_plan.tree_string(),
                            "Data skipping index applied.")
            if ctx is not None:
                ctx.applied.extend(sorted(set(applied)))
        return new_plan

    def _try_prune(self, session, scan: Scan, condition: E.Expr,
                   candidates: List[IndexLogEntry],
                   applied: List[str], ctx=None) -> Optional[Scan]:
        relation = get_relation(session, scan)
        if relation is None:
            return None
        with _trace.span(SN.SOURCE_CHECK):
            all_files = relation.all_files()
        keep = np.ones(len(all_files), dtype=bool)
        hit_names: List[str] = []
        for entry in candidates:
            sig = _plan_signature(entry, scan)
            recorded = entry.signature.signatures[0].value \
                if entry.signature.signatures else None
            if sig is None or recorded is None or sig != recorded:
                if ctx is not None:
                    ctx.add("SOURCE_DATA_CHANGED", entry,
                            "Source fingerprint mismatch; refresh the "
                            "data-skipping index.")
                continue
            verdict = evaluate_sketch_predicate(entry, condition, all_files,
                                                relation.schema)
            if verdict is None:
                if ctx is not None:
                    sketched = sorted({s.column for s in
                                       entry.derivedDataset.sketches})
                    ctx.add("NO_APPLICABLE_SKETCH", entry,
                            f"No filter conjunct is refutable by the "
                            f"index's sketches (sketched columns: "
                            f"{sketched}); only literal comparisons and "
                            f"IN lists on a sketched column can prune.")
                continue
            keep &= verdict
            hit_names.append(entry.name)
        if not hit_names or keep.all():
            return None  # nothing pruned → no rewrite, no usage event.
        applied.extend(hit_names)
        kept_files = [f for f, k in zip(all_files, keep) if k]
        # The note makes the pruning visible in golden plans + explain:
        # without it a skipped scan prints identically to the full scan.
        return Scan(relation.with_files(kept_files),
                    skipping_note=(f"{len(kept_files)}/{len(all_files)} "
                                   f"files after skipping"))


def evaluate_sketch_predicate(entry: IndexLogEntry, condition: E.Expr,
                              all_files: Sequence[str],
                              relation_schema) -> Optional[np.ndarray]:
    """Per-file keep mask from the entry's sketch table, or None when the
    predicate has no evaluable conjunct."""
    with _trace.span(SN.SKIPPING_PROBE, files=len(all_files)) as sp:
        keep = _evaluate_sketch_predicate(entry, condition, all_files,
                                          relation_schema)
        if sp is not None:
            sp.attrs["kept"] = len(all_files) if keep is None \
                else int(keep.sum())
        return keep


def _evaluate_sketch_predicate(entry: IndexLogEntry, condition: E.Expr,
                               all_files: Sequence[str],
                               relation_schema) -> Optional[np.ndarray]:
    table = _load_sketch_table(entry)
    by_file = {name: i for i, name in enumerate(table["_file"])}
    n_sketch = len(table["_file"])

    sketch_by_col = {}
    for s in entry.derivedDataset.sketches:
        sketch_by_col.setdefault(s.column, []).append(s)

    mask_rows: Optional[np.ndarray] = None
    for conjunct in E.split_conjunctive_predicates(condition):
        verdict = _eval_node(conjunct, table, sketch_by_col, relation_schema,
                             n_sketch)
        if verdict is not None:
            mask_rows = verdict if mask_rows is None else (mask_rows & verdict)
    if mask_rows is None:
        return None

    # Map sketch-row verdicts onto the scan's file list; files without a
    # sketch row (shouldn't happen on signature match) are kept.
    out = np.ones(len(all_files), dtype=bool)
    for i, f in enumerate(all_files):
        j = by_file.get(f)
        if j is not None:
            out[i] = bool(mask_rows[j])
    return out


def _eval_node(e: E.Expr, table, sketch_by_col, relation_schema,
               n: int) -> Optional[np.ndarray]:
    """Keep mask over sketch rows for one predicate node; None = unknown."""
    if isinstance(e, E.And):
        l = _eval_node(e.left, table, sketch_by_col, relation_schema, n)
        r = _eval_node(e.right, table, sketch_by_col, relation_schema, n)
        if l is None:
            return r
        if r is None:
            return l
        return l & r
    if isinstance(e, E.Or):
        l = _eval_node(e.left, table, sketch_by_col, relation_schema, n)
        r = _eval_node(e.right, table, sketch_by_col, relation_schema, n)
        if l is None or r is None:
            return None  # one side unprunable → the OR can't prune.
        return l | r
    if isinstance(e, E.In) and isinstance(e.value, E.Col) \
            and all(isinstance(o, E.Lit) for o in e.options):
        verdicts = [_eval_compare(e.value.column, "EqualTo", o.value, table,
                                  sketch_by_col, relation_schema, n)
                    for o in e.options]
        if any(v is None for v in verdicts) or not verdicts:
            return None
        out = verdicts[0]
        for v in verdicts[1:]:
            out = out | v
        return out
    if isinstance(e, (E.EqualTo, E.LessThan, E.LessThanOrEqual,
                      E.GreaterThan, E.GreaterThanOrEqual)):
        left, right = e.left, e.right
        flipped = False
        if isinstance(left, E.Lit) and isinstance(right, E.Col):
            left, right = right, left
            flipped = True
        if not (isinstance(left, E.Col) and isinstance(right, E.Lit)):
            return None
        op = type(e).__name__
        if flipped:
            op = {"EqualTo": "EqualTo", "LessThan": "GreaterThan",
                  "LessThanOrEqual": "GreaterThanOrEqual",
                  "GreaterThan": "LessThan",
                  "GreaterThanOrEqual": "LessThanOrEqual"}[op]
        return _eval_compare(left.column, op, right.value, table,
                             sketch_by_col, relation_schema, n)
    return None


def _eval_compare(column: str, op: str, value, table, sketch_by_col,
                  relation_schema, n: int) -> Optional[np.ndarray]:
    from ..actions.create_skipping import (bloom_col, minmax_cols,
                                           valuelist_col)

    sketches: List[Sketch] = sketch_by_col.get(column, [])
    if not sketches:
        return None
    out: Optional[np.ndarray] = None

    def apply_mask(m: np.ndarray):
        nonlocal out
        out = m if out is None else (out & m)

    from .. import native

    # Probe-ready arrays (bitset matrices, converted min/max columns) are
    # assembled once per sketch table and cached inside it — at lake scale
    # (thousands of files) the per-probe Python assembly otherwise costs
    # more than the scan it would save (r4 lake bench: 230 ms/probe at
    # 1600 files, vs microseconds prepared).
    prep_cache = table.setdefault("__prepared__", {})

    for s in sketches:
        if s.kind == "MinMax":
            lo_name, hi_name = minmax_cols(column)
            lo, hi = table[lo_name], table[hi_name]
            dtype = relation_schema.field(column).dtype
            # Native (or vectorized) prune over all files in one call; the
            # generic Python loop remains for unsupported dtypes (strings).
            pr = prep_cache.get((column, "MinMax"))
            if pr is None:
                pr = native.prepare_minmax(lo, hi, dtype)
                prep_cache[(column, "MinMax")] = \
                    pr if pr is not None else "unsupported"
            m = None if pr in (None, "unsupported") else \
                native.minmax_prune_prepared(pr, op, value, dtype)
            if m is None:
                m = np.ones(n, dtype=bool)
                for i in range(n):
                    if lo[i] is None or hi[i] is None:
                        continue  # all-null file: only IS NULL matches; keep.
                    if op == "EqualTo":
                        m[i] = lo[i] <= value <= hi[i]
                    elif op == "LessThan":
                        m[i] = lo[i] < value
                    elif op == "LessThanOrEqual":
                        m[i] = lo[i] <= value
                    elif op == "GreaterThan":
                        m[i] = hi[i] > value
                    elif op == "GreaterThanOrEqual":
                        m[i] = hi[i] >= value
            apply_mask(m)
        elif s.kind == "ValueList" and op == "EqualTo":
            lists = table[valuelist_col(column)]
            m = np.ones(n, dtype=bool)
            for i, vals in enumerate(lists):
                if vals is None:
                    continue  # over-cardinality file: no information, keep
                m[i] = value in vals  # exact membership, no false positives
            apply_mask(m)
        elif s.kind == "BloomFilter" and op == "EqualTo":
            dtype = relation_schema.field(column).dtype
            num_bits = int(s.properties["numBits"])
            num_hashes = int(s.properties["numHashes"])
            pr = prep_cache.get((column, "BloomFilter"))
            if pr is None:
                pr = native.prepare_bloom(table[bloom_col(column)],
                                          num_bits)
                prep_cache[(column, "BloomFilter")] = pr
                # The raw bitset pylist is equal-sized to the prepared
                # matrix and never read again — drop it so the cached
                # table doesn't hold bloom bytes twice.
                table[bloom_col(column)] = None
            m = native.bloom_probe_prepared(pr[0], pr[1], value, dtype,
                                            num_bits, num_hashes)
            apply_mask(m)
    return out


# Tiny per-entry cache keyed on (sketch file path, log id): sketch tables
# are small and reread per query otherwise. The path lies under the
# index's own directory, so two lakes with same-named indexes never share
# an entry.
_SKETCH_CACHE: dict = {}


def _load_sketch_table(entry: IndexLogEntry) -> dict:
    from ..actions.create_skipping import SKETCH_FILE_NAME

    files = [f for f in entry.content.files
             if os.path.basename(f) == SKETCH_FILE_NAME]
    key = (files[0], entry.id)
    cached = _SKETCH_CACHE.get(key)
    if cached is not None:
        return cached
    from ..index import data_store
    _fs, _p0 = data_store.fs_and_path(files[0])
    t = pq.read_table(_p0, filesystem=_fs)
    table = {name: t.column(name).to_pylist() for name in t.column_names}
    if len(_SKETCH_CACHE) >= 8:  # keep at most a handful of entries alive.
        _SKETCH_CACHE.pop(next(iter(_SKETCH_CACHE)))
    _SKETCH_CACHE[key] = table
    return table
