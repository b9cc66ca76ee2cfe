"""Whole-plan fusion: ONE XLA program per (region fingerprint, shape class).

The staged executor runs operator-at-a-time: every plan node materializes
a host ``Table``, so a filter→project→join-probe→aggregate chain pays a
separate dispatch (and its host round trip) per stage — the overhead
Flare (PAPERS.md, arxiv 1703.08219) eliminates in Spark by compiling the
whole query instead of stitching per-operator programs. This module is
the single-device counterpart of the SPMD tier's fused mesh programs
(execution/spmd.py): a fusion planner walks the optimized plan, carves
it into maximal fusible regions, and compiles each region into ONE
jitted program registered in the process-wide ProgramBank keyed
``(region fingerprint, shape-class vector)`` — so intermediates never
cross the host ``Table`` boundary and a warm region re-dispatches with
zero compiles.

Region shape (mirroring the SPMD chain grammar)::

    [Aggregate (grouped or global, no COUNT DISTINCT)]
      └─ {Filter, Project, inner/semi/anti single-key equi-Join}*
           └─ Scan | IndexScan | <any barrier subtree, executed staged>

Execution model — mask-based streaming with static shapes (the r07
padding contract): the stream loads once at its length class; filters
AND into a keep mask instead of compacting; joins probe a prepared
(sorted, key-unique for inner) side with a searchsorted and gather its
columns in place; the aggregate sorts kept rows by the group keys inside
the program and segments into capacity-bounded slots. Exactly ONE scalar
leaves the program per execution (the survivor/group count), where the
staged pipeline paid one per stage. Literal values of slot-fusable
predicates ride as runtime scalar arguments (the r07 contract), so a
literal sweep reuses one compiled region.

Byte-identity: the fused program replays the staged operator semantics
step for step — the same stable sorts over the same null-aware keys, the
same segment ops over rows in the same order — so answers are
byte-identical to staged execution (asserted over verbatim TPC-H/TPC-DS
in tests/test_fusion.py). Anything the program does not absorb falls
back per-stage at a named boundary (execution/fusion_boundaries.py,
frozen registry): sorts, windows, outer/cross joins, COUNT DISTINCT,
chunked (over-budget) sources, bucket-ordered streams (the staged
executor owns the covering-index fast paths), and literal-sweep batches.
``hyperspace.tpu.execution.fusion.enabled=false`` restores pure staged
execution.

The fusion attempt runs only where the distributed tier declined — the
mesh keeps right of way — and compiles ONLY through the ProgramBank
(ops/kernels.run_fused_region; scripts/lint.py pins jax.jit sites).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..exceptions import HyperspaceException, QueryDeadlineError
from ..plan import expr as E
from ..plan.nodes import (Aggregate, BucketUnion, Filter, IndexScan, Join,
                          Limit, LogicalPlan, Project, Scan, Sort, Union,
                          Window, infer_dtype)
from ..schema import FLOAT64, INT64, STRING
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace
from . import fusion_boundaries as FB
from . import shapes
from .columnar import (_DEVICE_DTYPE, Column, Table, dictionaries_equal,
                       translate_codes)
from .evaluator import _pred_eval, eval_expr, predicate_slots

# Fused region executions in this process (tests/bench assert the path is
# actually taken, the spmd.DISPATCH_COUNT convention).
DISPATCH_COUNT = 0

_FUSABLE_AGGS = (E.Count, E.Sum, E.Avg, E.Min, E.Max)


class _FuseFallback(Exception):
    """Runtime bailout on an otherwise fusible region; ``kind`` names the
    boundary (fusion_boundaries registry) and the staged executor re-runs
    the region byte-identically. ``node`` (when the bailout is pinned to
    one plan node — a duplicate-keyed join side, a chunked/bucket-ordered
    leaf) gets marked so the staged descent's sub-region attempts skip
    it instead of repeating its IO/prep per chain node."""

    def __init__(self, kind: str, node: Optional[LogicalPlan] = None):
        super().__init__(kind)
        self.kind = kind
        self.node = node


class _FusionState:
    """Process-wide counters + the poisoned-region memo (a region whose
    fused program failed once stays staged instead of re-failing per
    query). Lives in one object so the module-level mutable-state lint
    gate stays clean."""

    def __init__(self):
        self.lock = threading.Lock()
        self.boundaries: Dict[str, int] = {}
        self.poisoned: Set[tuple] = set()
        self.fused_nodes_total = 0

    def stats(self) -> dict:
        with self.lock:
            return {
                "fused_executions": DISPATCH_COUNT,
                "fused_nodes_total": self.fused_nodes_total,
                "fallbacks": dict(self.boundaries),
                "poisoned_regions": len(self.poisoned),
            }


_STATE = _FusionState()


def _bump(kind: str) -> None:
    with _STATE.lock:
        _STATE.boundaries[kind] = _STATE.boundaries.get(kind, 0) + 1


def note_boundary(kind: str) -> None:
    """Count a region boundary / fallback by kind (frozen registry —
    scripts/lint.py rejects free-form kinds at these call sites)."""
    _bump(kind)


def stats() -> dict:
    return _STATE.stats()


def reset_stats() -> None:
    """Tests only: zero the counters (the poisoned memo survives — a
    broken region stays broken across tests in one process)."""
    global DISPATCH_COUNT
    with _STATE.lock:
        _STATE.boundaries.clear()
        _STATE.fused_nodes_total = 0
        DISPATCH_COUNT = 0


# ---------------------------------------------------------------------------
# Region planning (pure plan-shape analysis; no IO).
# ---------------------------------------------------------------------------

_BARRIER_KINDS = {
    Sort: FB.SORT, Window: FB.WINDOW, Limit: FB.LIMIT, Union: FB.UNION,
    BucketUnion: FB.UNION, Aggregate: FB.AGGREGATE,
}


class _Region:
    """A planned fusible region: ``stages`` bottom-up over ``bottom``
    (a leaf or a staged barrier subtree), optional ``agg`` root."""

    def __init__(self, stages: List[tuple], bottom: LogicalPlan,
                 agg: Optional[Aggregate], root: LogicalPlan):
        self.stages = stages  # bottom-up [("filter"|"project"|"join", ...)]
        self.bottom = bottom
        self.agg = agg
        self.root = root

    @property
    def node_count(self) -> int:
        return len(self.stages) + (1 if self.agg is not None else 0)


def _strip_alias(e: E.Expr) -> E.Expr:
    while isinstance(e, E.Alias):
        e = e.child
    return e


def _normalized_pair(node: Join) -> Optional[Tuple[str, str]]:
    """The single (left, right) equi-join key pair, or None (barrier)."""
    pairs = E.extract_equi_join_keys(node.condition)
    if pairs is None:
        note_boundary(FB.NON_EQUI_JOIN)
        return None
    if len(pairs) != 1:
        note_boundary(FB.MULTI_KEY_JOIN)
        return None
    a, b = pairs[0]
    left_names = set(node.left.schema.names)
    right_names = set(node.right.schema.names)
    if a in left_names and b in right_names:
        return a, b
    if b in left_names and a in right_names:
        return b, a
    note_boundary(FB.NON_EQUI_JOIN)
    return None


def _plan_region(root: LogicalPlan, session) -> Optional[_Region]:
    agg = None
    node = root
    if isinstance(node, Aggregate):
        child_schema = node.child.schema
        for a in node.aggs:
            inner = _strip_alias(a)
            if isinstance(inner, E.CountDistinct):
                note_boundary(FB.COUNT_DISTINCT)
                return None
            if not isinstance(inner, _FUSABLE_AGGS):
                note_boundary(FB.UNSUPPORTED_AGG)
                return None
            # Statically decidable dtype constraints — checked HERE so a
            # doomed region never pays leaf IO / side prep first: string
            # sum/avg is an error either way (staged raises it too), and
            # a STRING min/max output needs a plain-Col child whose
            # dictionary the host can re-attach.
            try:
                if isinstance(inner, (E.Sum, E.Avg)) \
                        and infer_dtype(inner.child, child_schema) \
                        == STRING:
                    note_boundary(FB.UNSUPPORTED_AGG)
                    return None
                if isinstance(inner, (E.Min, E.Max)) \
                        and infer_dtype(inner, child_schema) == STRING \
                        and not isinstance(_strip_alias(inner.child),
                                           E.Col):
                    note_boundary(FB.UNSUPPORTED_AGG)
                    return None
            except HyperspaceException:
                note_boundary(FB.UNSUPPORTED_AGG)
                return None
        agg = node
        node = node.child
    stages_td: List[tuple] = []
    while isinstance(node, (Filter, Project, Join)):
        if isinstance(node, Filter):
            stages_td.append(("filter", node))
            node = node.child
        elif isinstance(node, Project):
            stages_td.append(("project", node))
            node = node.child
        else:
            if getattr(node, "_fusion_skip", None) is not None:
                # This join bailed at runtime before (duplicate probe
                # keys, empty/odd side): stop the chain here — stages
                # ABOVE still fuse over the staged join's output.
                break
            jt = node.join_type
            if jt == "cross":
                note_boundary(FB.CROSS_JOIN)
                break
            if jt in ("left", "right", "full"):
                note_boundary(FB.OUTER_JOIN)
                break
            pair = _normalized_pair(node)
            if pair is None:
                break
            stages_td.append(("join", node, pair))
            node = node.left
    skip = getattr(node, "_fusion_skip", None)
    if skip is not None:
        _bump(skip)  # kinds recorded at the original runtime bailout
        if isinstance(node, (Scan, IndexScan)):
            # A marked LEAF is the stream itself (chunked / bucket
            # order): no region over it can fuse.
            return None
    elif isinstance(node, (Scan, IndexScan)):
        if isinstance(node, IndexScan) and node.use_bucket_spec:
            # Bucket-spec index scans feed the staged shuffle-free merge
            # join / sort-skipping group-by — fast paths the fused program
            # does not replay. Decide statically, before any IO.
            note_boundary(FB.BUCKET_ORDER)
            return None
        note_boundary(FB.LEAF)
    else:
        barrier = _BARRIER_KINDS.get(type(node))
        if barrier is None:
            note_boundary(FB.UNSUPPORTED_EXPR)
        else:
            _bump(barrier)  # kinds from the _BARRIER_KINDS FB.* table
    min_stages = max(2, session.hs_conf.fusion_min_stages())
    region = _Region(list(reversed(stages_td)), node, agg, root)
    if region.node_count < min_stages:
        note_boundary(FB.REGION_TOO_SMALL)
        return None
    return region


def _region_needs(region: _Region, out_names: List[str]):
    """Top-down column-need analysis: the bottom subtree's needed set and
    each join stage's right-side needed set (keys included — the side must
    materialize them to build probe codes)."""
    if region.agg is not None:
        needed: Set[str] = set(region.agg.group_cols)
        for a in region.agg.aggs:
            needed |= set(a.references)
    else:
        needed = set(out_names)
    right_needed: Dict[int, Set[str]] = {}
    for i in range(len(region.stages) - 1, -1, -1):
        st = region.stages[i]
        kind, node = st[0], st[1]
        if kind == "filter":
            needed |= set(node.condition.references)
        elif kind == "project":
            # Mirror the staged executor: EVERY project expr evaluates
            # (XLA dead-code-eliminates unconsumed outputs for free).
            below: Set[str] = set()
            for e in node.exprs:
                below |= set(e.references)
            needed = below
        else:
            lname, rname = st[2]
            if node.join_type in ("semi", "anti"):
                right_needed[i] = {rname}
                needed = needed | {lname}
            else:
                rnames = set(node.right.schema.names)
                right_needed[i] = {n for n in needed if n in rnames} | {rname}
                needed = {n for n in needed if n not in rnames} | {lname}
    return needed, right_needed


# ---------------------------------------------------------------------------
# Runtime prep: leaf load, join-side preparation, fingerprint + args.
# ---------------------------------------------------------------------------

def _leaf_within_budget(leaf, session) -> bool:
    """Mirror of spmd._leaf_within_budget: a leaf past the chunk budget
    belongs to the streaming (chunked) staged path, never to a program
    that materializes it whole."""
    from .columnar import parquet_row_counts
    try:
        if isinstance(leaf, IndexScan):
            total = sum(parquet_row_counts(
                list(leaf.index_entry.content.files)
                + list(leaf.appended_files)))
        else:
            relation = leaf.relation
            fmt = getattr(relation, "data_file_format", relation.file_format)
            if fmt != "parquet":
                return True
            total = sum(parquet_row_counts(relation.all_files()))
    except Exception:
        return True
    return total <= session.hs_conf.max_chunk_rows()


def _load_leaf(leaf, lead_filters, needed, ex) -> Table:
    """Materialize the stream leaf with the same IO pruning the staged
    Filter-over-leaf branch applies: filter stages sitting directly above
    the leaf push their row-group-prunable conjuncts into the read (the
    full mask re-applies on device, so the pruned read is byte-identical).
    The spmd._load_leaf contract, single-device."""
    conds = [n.condition for n in lead_filters]
    if conds:
        from .pushdown import pruned_index_read_filter, pushable_filter
        combined = conds[0]
        for c in conds[1:]:
            combined = E.And(combined, c)
        if isinstance(leaf, IndexScan):
            pa_filter = pruned_index_read_filter(
                leaf.index_entry, combined, leaf.schema)
            if pa_filter is not None:
                table = ex._execute_index_scan(
                    leaf, needed, pa_filter, prefer_pruned_read=True)
                if table.num_rows > 0:
                    return table
        else:
            pa_filter = pushable_filter(combined, leaf.schema,
                                        allow_nested=False)
            if pa_filter is not None:
                table = ex._execute_scan(leaf, needed, pa_filter)
                if table.num_rows > 0:
                    return table
    return ex._execute(leaf, needed)


def _dict_fp(dic: Optional[np.ndarray]):
    """Dictionary content fingerprint (spmd._dict_fingerprint precedent:
    dictionaries become trace-time constants — literal bounds, translate
    tables — so they key programs by VALUE)."""
    if dic is None:
        return None
    return tuple(dic.tolist())


def _empty_device(dtype) -> jax.Array:
    """Zero-length device array WITHOUT a compile: jnp.zeros lowers a
    one-off broadcast_in_dim/convert program per dtype (the first thing a
    cold boot would pay for), device_put of a host array is a transfer."""
    return jax.device_put(np.zeros(0, dtype))


def _tiny(meta: Dict[str, Tuple[str, Optional[np.ndarray], bool]]
          ) -> Dict[str, Column]:
    """Zero-length columns carrying (dtype, dictionary, nullability) —
    the metadata-propagation trick the SPMD prep walk uses."""
    return {n: Column(dt, _empty_device(_DEVICE_DTYPE[dt]),
                      _empty_device(np.bool_) if nul else None, dic)
            for n, (dt, dic, nul) in meta.items()}


def _meta_of(table_or_cols) -> Dict[str, Tuple]:
    cols = table_or_cols.columns if isinstance(table_or_cols, Table) \
        else table_or_cols
    return {n: (c.dtype, c.dictionary, c.validity is not None)
            for n, c in cols.items()}


def _dtype_max_np(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return np.inf
    if dtype == jnp.bool_:
        return True
    return jnp.iinfo(dtype).max


class _SidePrep:
    """A prepared join side: ``keys`` ascending (class-padded with the
    dtype max so the searchsorted precondition holds over the pad tail),
    ``cols`` row-aligned data columns (inner joins only), ``n`` the valid
    key count. Inner sides are key-unique (checked, one host sync)."""

    def __init__(self, keys, n: int, col_order: List[str],
                 cols: Dict[str, Column]):
        self.keys = keys
        self.n = n
        self.col_order = col_order
        self.cols = cols


def _prepare_side(node: Join, pair, tiny: Dict[str, Column],
                  right_needed: Set[str], ex) -> Tuple[_SidePrep, tuple]:
    """Execute + key-sort one join side; returns (prep, descriptor)."""
    from ..ops import kernels
    lname, rname = pair
    jt = node.join_type
    keys_only = jt in ("semi", "anti")
    right = ex._execute(node.right, set(right_needed)).compact()
    if right.num_rows == 0:
        raise _FuseFallback(FB.EMPTY_INPUT, node)
    rk = right.column(rname)
    lcol = tiny[lname]
    if (lcol.dtype == STRING) != (rk.dtype == STRING):
        raise _FuseFallback(FB.KEY_DTYPE, node)
    if rk.validity is not None:
        # Inner/semi/anti: null side keys never match — drop them up
        # front, exactly like the staged join paths.
        right = right.filter(rk.validity)
        if right.num_rows == 0:
            raise _FuseFallback(FB.EMPTY_INPUT, node)
        rk = right.column(rname)
    if rk.dtype == STRING:
        codes = rk.data if dictionaries_equal(lcol.dictionary, rk.dictionary) \
            else translate_codes(lcol.dictionary, rk)
        promo = jnp.int32
    else:
        try:
            promo = jnp.promote_types(_DEVICE_DTYPE[lcol.dtype],
                                      rk.data.dtype)
        except TypeError:
            raise _FuseFallback(FB.KEY_DTYPE, node)
        if not (jnp.issubdtype(promo, jnp.integer)
                or jnp.issubdtype(promo, jnp.floating)):
            raise _FuseFallback(FB.KEY_DTYPE, node)
        codes = rk.data if rk.data.dtype == promo \
            else kernels.cast_array(rk.data, promo)
    order = kernels.lex_sort_indices([codes], pad=False)
    codes = kernels.gather_arrays(order, (codes,))[0]
    n_side = int(codes.shape[0])
    if jt == "inner" and n_side > 1 \
            and bool(kernels.has_adjacent_duplicates(codes)):  # HOST SYNC
        # m:n join: the mask-streaming program cannot expand matches —
        # the staged merge join owns it.
        raise _FuseFallback(FB.DUPLICATE_PROBE_KEYS, node)
    cls = shapes.padded_length(n_side)
    keys = shapes.pad_to(codes, cls, fill=_dtype_max_np(codes.dtype))
    cols: Dict[str, Column] = {}
    col_order: List[str] = []
    if not keys_only:
        right = right.take(order)
        for n in right.names:
            c = right.column(n)
            data = shapes.pad_to(c.data, cls)
            validity = None if c.validity is None \
                else shapes.pad_to(c.validity, cls, fill=False)
            cols[n] = Column(c.dtype, data, validity, c.dictionary)
            col_order.append(n)
    descr = ("J", jt, lname, rname, str(keys.dtype),
             tuple((n, c.dtype, _dict_fp(c.dictionary),
                    c.validity is not None)
                   for n, c in cols.items()))
    return _SidePrep(keys, n_side, col_order, cols), descr


class _RegionSpec:
    """Everything the traced builder needs, fully determined by ``key``:
    bottom-up stage program, stream column metadata/order, side layouts,
    aggregate description, output names."""

    def __init__(self, stages, col_order, col_meta, out_names, agg,
                 group_cols, key):
        self.stages = stages        # bottom-up builder stage tuples
        self.col_order = col_order  # stream column name order
        self.col_meta = col_meta    # name -> (dtype, dict, nullable)
        self.out_names = out_names
        self.agg = agg              # Aggregate node or None
        self.group_cols = group_cols
        self.key = key


# ---------------------------------------------------------------------------
# The traced program body (runs under ONE jax.jit via the ProgramBank).
# ---------------------------------------------------------------------------

def _null_aware(c: Column) -> List:
    """executor._null_aware_keys, inlined (nulls sort first)."""
    if c.validity is None:
        return [c.data]
    return [c.validity.astype(jnp.int32),
            jnp.where(c.validity, c.data, jnp.zeros((), c.data.dtype))]


def _sum_out_dtype(sums) -> str:
    return FLOAT64 if jnp.issubdtype(sums.dtype, jnp.floating) else INT64


def _sentinel(dtype, maxval: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        info = jnp.finfo(dtype)
    else:
        info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if maxval else info.min, dtype)


def _traced_agg(agg_expr: E.Expr, stable: Table, gids, num_segments: int
                ) -> Column:
    """Mirror of executor._eval_agg over traced inputs: identical
    widening, null-sentinel substitution, valid counting, and mean
    division — and identical per-segment accumulation ORDER (rows arrive
    group-sorted, non-routed rows park at an out-of-range id), so sums
    are bitwise equal to the staged path's."""
    import jax

    agg = _strip_alias(agg_expr)
    if isinstance(agg, E.Count):
        if agg.child is None:
            ones = jnp.ones(gids.shape[0], jnp.int64)
        else:
            c = eval_expr(stable, agg.child)
            ones = jnp.ones(gids.shape[0], jnp.int64) if c.validity is None \
                else c.validity.astype(jnp.int64)
        return Column(INT64, jax.ops.segment_sum(
            ones, gids, num_segments=num_segments))
    child = eval_expr(stable, agg.child)
    validity = child.validity
    counts = None
    if validity is not None or isinstance(agg, E.Avg):
        ones = jnp.ones(gids.shape[0], jnp.int64) if validity is None \
            else validity.astype(jnp.int64)
        counts = jax.ops.segment_sum(ones, gids, num_segments=num_segments)
    out_validity = (counts > 0) if validity is not None else None
    if isinstance(agg, (E.Sum, E.Avg)):
        acc = child.data.astype(jnp.float64) \
            if jnp.issubdtype(child.data.dtype, jnp.floating) \
            else child.data.astype(jnp.int64)
        if validity is not None:
            acc = jnp.where(validity, acc, jnp.zeros((), acc.dtype))
        sums = jax.ops.segment_sum(acc, gids, num_segments=num_segments)
        if isinstance(agg, E.Sum):
            return Column(_sum_out_dtype(sums), sums, out_validity)
        return Column(FLOAT64,
                      sums.astype(jnp.float64)
                      / jnp.maximum(counts, 1).astype(jnp.float64),
                      out_validity)
    is_min = isinstance(agg, E.Min)
    data = child.data
    if validity is not None:
        data = jnp.where(validity, data, _sentinel(data.dtype, is_min))
    fn = jax.ops.segment_min if is_min else jax.ops.segment_max
    return Column(child.dtype,
                  fn(data, gids, num_segments=num_segments),
                  out_validity, child.dictionary)


def _make_builder(spec: _RegionSpec):
    """The fused program body. Pure function of ``spec`` (== the bank
    key), as the ProgramBank contract requires."""

    def fused_region(args):
        # A stable program name (the HLO module ``jit_fused_region``) and
        # scope, so a profile reads the same from compile to compile.
        with jax.named_scope("fused_region"):
            return run(args)

    def run(args):
        n, col_arrays, lit_stages, sides = args
        cols: Dict[str, Column] = {}
        for name, (data, validity) in zip(spec.col_order, col_arrays):
            dt, dic, _nul = spec.col_meta[name]
            cols[name] = Column(dt, data, validity, dic)
        phys = int(col_arrays[0][0].shape[0])
        iota = jnp.arange(phys, dtype=jnp.int32)
        keep = iota < n
        out: Dict[str, jnp.ndarray] = {}
        lit_i = 0
        side_i = 0
        for st in spec.stages:
            kind = st[0]
            if kind == "fslot":
                _, refs, pspec = st
                pcols = tuple((cols[nm].data, cols[nm].validity)
                              for nm in refs)
                data, validity = _pred_eval(pspec, pcols,
                                            lit_stages[lit_i])
                lit_i += 1
                mask = data if validity is None else (data & validity)
                keep = keep & mask
            elif kind == "frepr":
                _, cond = st
                c = eval_expr(Table(dict(cols)), cond)
                mask = c.data if c.validity is None \
                    else (c.data & c.validity)
                keep = keep & mask
            elif kind == "project":
                _, node = st
                t = Table(dict(cols))
                cols = {e.name: eval_expr(t, e) for e in node.exprs}
            else:  # join
                _, node, pair, jid, side_meta = st
                lname, _rname = pair
                keys, n_side, side_arrays = sides[side_i]
                side_i += 1
                lc = cols[lname]
                lk = lc.data if lc.dtype == STRING \
                    else lc.data.astype(keys.dtype)
                lvalid = lc.validity
                lo = jnp.minimum(jnp.searchsorted(keys, lk, side="left"),
                                 n_side)
                hi = jnp.minimum(jnp.searchsorted(keys, lk, side="right"),
                                 n_side)
                matched = lo < hi
                if lvalid is not None:
                    matched = matched & lvalid
                if node.join_type == "inner":
                    keep = keep & matched
                    pos = jnp.clip(lo, 0, keys.shape[0] - 1).astype(jnp.int32)
                    for (sname, sdt, sdic, snul), (sdata, svalid) in zip(
                            side_meta, side_arrays):
                        data = jnp.take(sdata, pos, axis=0, mode="clip")
                        validity = None if svalid is None else \
                            jnp.take(svalid, pos, axis=0, mode="clip")
                        cols[sname] = Column(sdt, data, validity, sdic)
                    # Observed join output rows (the staged path's
                    # _record_join_actual feed): kept-so-far ∧ matched.
                    out[f"jrows:{jid}"] = jnp.sum(keep.astype(jnp.int64))
                elif node.join_type == "semi":
                    keep = keep & matched
                else:  # anti: null left keys never match -> kept
                    keep = keep & ~matched

        if spec.agg is None:
            out["mask"] = keep
            out["count"] = jnp.sum(keep)
            for nm in spec.out_names:
                c = cols[nm]
                out[f"o:{nm}"] = c.data
                if c.validity is not None:
                    out[f"ov:{nm}"] = c.validity
            return out

        if not spec.group_cols:
            # Global aggregate: one segment, non-kept rows parked at the
            # dropped out-of-range id (executor._execute_global_aggregate
            # over a class-padded table, with the filter mask folded in).
            gids = jnp.where(keep, jnp.int32(0), jnp.int32(phys))
            stable = Table(dict(cols))
            for a in spec.agg.aggs:
                col = _traced_agg(a, stable, gids, 1)
                out[f"a:{a.name}"] = col.data
                if col.validity is not None:
                    out[f"av:{a.name}"] = col.validity
            out["ng"] = jnp.int32(1)
            return out

        # Grouped aggregate: stable-sort kept rows by the null-aware group
        # keys (non-kept rows last via the leading ~keep key — the valid
        # prefix is byte-identical to the staged sort of the compacted
        # survivors), then segment into capacity-`phys` slots.
        from ..ops import kernels
        key_cols = [cols[g] for g in spec.group_cols]
        sort_keys = [(~keep).astype(jnp.int32)]
        for c in key_cols:
            sort_keys.extend(_null_aware(c))
        order = kernels.lex_sort_indices(sort_keys)
        keep_s = jnp.take(keep, order)
        scols = {nm: Column(c.dtype,
                            jnp.take(c.data, order, axis=0, mode="clip"),
                            None if c.validity is None
                            else jnp.take(c.validity, order, axis=0,
                                          mode="clip"),
                            c.dictionary)
                 for nm, c in cols.items()}
        skeys = []
        for g in spec.group_cols:
            skeys.extend(_null_aware(scols[g]))
        change = jnp.zeros(phys, jnp.bool_)
        for k in skeys:
            change = change | jnp.concatenate(
                [jnp.zeros(1, jnp.bool_), k[1:] != k[:-1]])
        change = change & keep_s
        gids = jnp.cumsum(change.astype(jnp.int32))
        last = jnp.max(jnp.where(keep_s, gids, 0))
        ng = jnp.where(jnp.any(keep_s), last + 1, 0).astype(jnp.int32)
        gids = jnp.where(keep_s, gids, jnp.int32(phys))
        out["ng"] = ng
        firsts = jax.ops.segment_min(iota, gids, num_segments=phys)
        for g in spec.group_cols:
            c = scols[g]
            out[f"g:{g}"] = jnp.take(c.data, firsts, axis=0, mode="clip")
            if c.validity is not None:
                out[f"gv:{g}"] = jnp.take(c.validity, firsts, axis=0,
                                          mode="clip")
        stable = Table(dict(scols))
        for a in spec.agg.aggs:
            col = _traced_agg(a, stable, gids, phys)
            out[f"a:{a.name}"] = col.data
            if col.validity is not None:
                out[f"av:{a.name}"] = col.validity
        return out

    return fused_region


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------

def try_execute(plan: LogicalPlan, needed: Optional[Set[str]]
                ) -> Optional[Table]:
    """Fuse-and-execute the maximal region rooted at ``plan``, or return
    None for the staged executor. Called from executor._execute for chain
    roots and from the Aggregate branch AFTER the SPMD attempt (the
    distributed tier keeps right of way)."""
    from . import executor as ex
    session = ex._SESSION.get()
    if session is None:
        return None
    if not session.hs_conf.fusion_enabled():
        note_boundary(FB.DISABLED)
        return None
    from ..serving import batcher
    if batcher.active_sweep() is not None:
        # Literal-sweep batches collapse members into ONE vmapped staged
        # invocation over shared scans — their win, their path.
        note_boundary(FB.SWEEP)
        return None
    region = _plan_region(plan, session)
    if region is None:
        return None
    try:
        return _execute_region(region, needed, session, ex)
    except _FuseFallback as f:
        _bump(f.kind)
        if f.node is not None:
            # Data-dependent bailout (duplicate probe keys, bucket
            # order, chunked source, ...): mark the responsible plan
            # node so the staged descent's sub-region attempts skip it
            # instead of repeating the leaf IO / side prep per chain
            # node. A pure perf hint — at worst (plan object memoized
            # across a data change) a now-fusible region stays staged.
            f.node._fusion_skip = f.kind
        return None
    except QueryDeadlineError:
        raise
    except Exception as e:
        from ..adaptive.feedback import ReplanRequested
        if isinstance(e, ReplanRequested):
            # Adaptive re-plan (staged joins can execute UNDER a fused
            # region via _execute_region's staged-bottom descent): a
            # control transfer to Session._execute_uncaptured, never a
            # fused-program failure to absorb.
            raise
        # A fused trace/compile failure must never fail the query: the
        # staged path re-runs the region byte-identically, and the region
        # key is poisoned so the failure is paid once, not per query —
        # UNLESS degradation is off (robustness.degrade.enabled=false,
        # the r14 fail-loud debugging contract): then the error surfaces.
        if not session.hs_conf.robustness_degrade_enabled():
            raise
        note_boundary(FB.FUSED_PROGRAM_ERROR)
        return None


def _execute_region(region: _Region, needed: Optional[Set[str]],
                    session, ex) -> Optional[Table]:
    root = region.root
    if region.agg is not None:
        out_names = list(region.agg.schema.names)
    else:
        out_names = [n for n in root.schema.names
                     if needed is None or n in needed] \
            or [root.schema.names[0]]
    bottom_needed, right_needed = _region_needs(region, out_names)

    # ---- stream ----------------------------------------------------------
    bottom = region.bottom
    if isinstance(bottom, (Scan, IndexScan)):
        if not _leaf_within_budget(bottom, session):
            raise _FuseFallback(FB.CHUNKED_SOURCE, bottom)
        lead_filters = []
        for st in region.stages:
            if st[0] != "filter":
                break
            lead_filters.append(st[1])
        stream = _load_leaf(bottom, lead_filters, bottom_needed, ex)
    else:
        stream = ex._execute(bottom, bottom_needed)
    if stream.bucket_order is not None:
        # The staged executor owns the covering-index fast paths (merge
        # join without sort, sort-skipping group-by) — and their output
        # row order.
        raise _FuseFallback(FB.BUCKET_ORDER, bottom)
    if stream.num_rows == 0 or stream.data_rows == 0 or not stream.columns:
        raise _FuseFallback(FB.EMPTY_INPUT)

    # ---- per-stage prep: metadata walk, slots, sides, fingerprint --------
    col_order = list(stream.names)
    tiny = _tiny(_meta_of(stream))
    builder_stages: List[tuple] = []
    descr: List[tuple] = []
    lit_values: List[tuple] = []
    side_preps: List[_SidePrep] = []
    from ..exceptions import HyperspaceException
    jid = 0
    try:
        for stage_i, st in enumerate(region.stages):
            kind, node = st[0], st[1]
            if kind == "filter":
                slots = predicate_slots(Table(tiny), node.condition)
                if slots is not None:
                    pspec, lits = slots
                    refs = tuple(sorted(set(node.condition.references)))
                    builder_stages.append(("fslot", refs, pspec))
                    descr.append(("F", refs, pspec))
                    lit_values.append(tuple(lits))
                else:
                    builder_stages.append(("frepr", node.condition))
                    descr.append(("F!", repr(node.condition)))
            elif kind == "project":
                t = Table(tiny)
                tiny = {e.name: eval_expr(t, e) for e in node.exprs}
                builder_stages.append(("project", node))
                descr.append(("P", tuple(repr(e) for e in node.exprs)))
            else:
                pair = st[2]
                prep, side_descr = _prepare_side(
                    node, pair, tiny, right_needed[stage_i], ex)
                side_meta = tuple(
                    (n, prep.cols[n].dtype, prep.cols[n].dictionary,
                     prep.cols[n].validity is not None)
                    for n in prep.col_order)
                builder_stages.append(("join", node, pair, jid, side_meta))
                descr.append(side_descr)
                side_preps.append(prep)
                jid += 1
                for n in prep.col_order:
                    c = prep.cols[n]
                    tiny[n] = Column(
                        c.dtype, _empty_device(_DEVICE_DTYPE[c.dtype]),
                        _empty_device(np.bool_)
                        if c.validity is not None else None,
                        c.dictionary)
        if region.agg is not None:
            # (Aggregate dtype constraints were checked statically in
            # _plan_region, before any IO.)
            descr.append(("A", tuple(region.agg.group_cols),
                          tuple((a.name, repr(a))
                                for a in region.agg.aggs)))
            for g in region.agg.group_cols:
                if g not in tiny:
                    raise _FuseFallback(FB.UNSUPPORTED_EXPR)
        else:
            for nm in out_names:
                if nm not in tiny:
                    raise _FuseFallback(FB.UNSUPPORTED_EXPR)
    except QueryDeadlineError:
        raise  # a cancellation is never a fallback (the r14 contract)
    except (HyperspaceException, KeyError):
        # Metadata walk hit an expression shape the evaluator rejects
        # (or a column the prep cannot see) — staged handles it.
        raise _FuseFallback(FB.UNSUPPORTED_EXPR)

    stream_meta = _meta_of(stream)
    key = ("region",
           tuple(descr),
           tuple((n,) + (stream_meta[n][0], _dict_fp(stream_meta[n][1]),
                         stream_meta[n][2])
                 for n in col_order),
           tuple(out_names))
    with _STATE.lock:
        poisoned = key in _STATE.poisoned
    if poisoned:
        raise _FuseFallback(FB.FUSED_PROGRAM_ERROR)

    spec = _RegionSpec(builder_stages, col_order, stream_meta, out_names,
                       region.agg, tuple(region.agg.group_cols)
                       if region.agg is not None else (), key)
    col_arrays = tuple((stream.columns[n].data, stream.columns[n].validity)
                       for n in col_order)
    sides = tuple((p.keys, p.n,
                   tuple((p.cols[n].data, p.cols[n].validity)
                         for n in p.col_order))
                  for p in side_preps)
    args = (stream.num_rows, col_arrays, tuple(lit_values), sides)
    shape_vec = ((int(stream.data_rows),)
                 + tuple(int(p.keys.shape[0]) for p in side_preps))

    final_meta = _meta_of(tiny)
    if _trace.idle():
        return _run_program(region, spec, key, shape_vec, args, final_meta,
                            session)
    with _trace.span(SN.EXEC_FUSED, fused_nodes=region.node_count,
                     root=root.node_name) as sp:
        table = _run_program(region, spec, key, shape_vec, args,
                             final_meta, session)
        if sp is not None:
            sp.attrs["rows"] = int(table.num_rows)
        return table


def _run_program(region: _Region, spec: _RegionSpec, key, shape_vec, args,
                 final_meta, session) -> Table:
    from ..ops import kernels
    global DISPATCH_COUNT
    try:
        out = kernels.run_fused_region(key, shape_vec,
                                       lambda: _make_builder(spec), args)
    except Exception as e:
        # Poison only genuine program defects (trace/compile errors that
        # would re-fail every query). Transient errors — OSError/timeout
        # and the robustness layer's injected faults (which surface here
        # through the bank's compile fault point) — must NOT permanently
        # demote the region to staged.
        from ..robustness.faults import InjectedFaultError
        if not isinstance(e, (OSError, TimeoutError, InjectedFaultError,
                              QueryDeadlineError)):
            with _STATE.lock:
                _STATE.poisoned.add(key)
        raise
    with _STATE.lock:
        # Under the state lock with the other fusion counters: fused
        # regions dispatch from concurrent serving workers, and an
        # unguarded += loses updates (HS302).
        DISPATCH_COUNT += 1
        _STATE.fused_nodes_total += region.node_count
    _record_actuals(region, out, session)
    if region.agg is None:
        return _finish_chain(spec, out, final_meta)
    if not spec.group_cols:
        return _finish_global(region.agg, out, final_meta)
    return _finish_grouped(region.agg, spec, out, final_meta)


def _record_actuals(region: _Region, out, session) -> None:
    """Per-join observed output rows into the r10/r13 actuals store, so
    the join-reorder q-error loop keeps learning from fused executions."""
    from ..serving import context as qctx
    jid = 0
    for st in region.stages:
        if st[0] != "join":
            continue
        node = st[1]
        rows_key = f"jrows:{jid}"
        jid += 1
        if node.join_type != "inner" or node.condition is None \
                or rows_key not in out:
            continue
        with _trace.span(SN.DEVICE_SYNC, node="fused.join_rows"):
            rows = int(out[rows_key])  # HOST SYNC (single scalar)
        key = qctx.join_actual_key(node.condition, node.left, node.right)
        ctx = qctx.active_context()
        if ctx is not None:
            ctx.record_join_actual(key, rows)
        elif session is not None:
            qctx.record_join_actual(session, key, rows)


def _finish_chain(spec: _RegionSpec, out, final_meta) -> Table:
    """Compact the masked stream exactly like the staged filter output:
    survivor count (the ONE scalar sync), class-padded gather indices,
    one fused gather."""
    from ..ops import kernels
    with _trace.span(SN.DEVICE_SYNC, node="fused.count"):
        m = int(out["count"])  # HOST SYNC (single scalar)
    cls = shapes.padded_length(m)
    idx = kernels.nonzero_pad_indices(out["mask"], cls)
    cols = {}
    for nm in spec.out_names:
        dt, dic, _nul = final_meta[nm]
        cols[nm] = Column(dt, out[f"o:{nm}"], out.get(f"ov:{nm}"), dic)
    return Table(cols).take(idx, valid_rows=m if cls != m else None)


def _agg_out_dict(agg_expr, final_meta):
    """The dictionary a STRING min/max output carries: its plain-Col
    child's (prep guaranteed the child IS a plain column)."""
    inner = _strip_alias(agg_expr)
    ref = _strip_alias(inner.child)
    if isinstance(ref, E.Col) and ref.column in final_meta:
        return final_meta[ref.column][1]
    return None


def _finish_global(agg: Aggregate, out, final_meta) -> Table:
    cols = {}
    for a in agg.aggs:
        f = agg.schema.field(a.name)
        dic = _agg_out_dict(a, final_meta) if f.dtype == STRING else None
        cols[a.name] = Column(f.dtype, out[f"a:{a.name}"],
                              out.get(f"av:{a.name}"), dic)
    return Table(cols)


def _finish_grouped(agg: Aggregate, spec: _RegionSpec, out,
                    final_meta) -> Table:
    with _trace.span(SN.DEVICE_SYNC, node="fused.groups"):
        ng = int(out["ng"])  # HOST SYNC (single scalar)
    if ng == 0:
        # Mirror executor._execute_aggregate's empty-result construction.
        cols = {}
        for f in agg.schema.fields:
            dt = f.dtype
            dic = None
            if f.name in final_meta and final_meta[f.name][0] == STRING:
                dic = final_meta[f.name][1]
            cols[f.name] = Column(
                dt, _empty_device(_DEVICE_DTYPE[dt]), None, dic)
        return Table(cols)
    cls = shapes.padded_length(ng)
    out_valid = ng if cls != ng else None
    from ..ops import kernels

    def fit(arr):
        if int(arr.shape[0]) == cls:
            return arr
        if int(arr.shape[0]) > cls:
            return kernels.slice_arrays((arr,), 0, cls)[0]
        return shapes.pad_to(arr, cls)

    cols = {}
    for g in spec.group_cols:
        dt, dic, _nul = final_meta[g]
        validity = out.get(f"gv:{g}")
        cols[g] = Column(dt, fit(out[f"g:{g}"]),
                         None if validity is None else fit(validity), dic)
    for a in agg.aggs:
        f = agg.schema.field(a.name)
        dic = _agg_out_dict(a, final_meta) if f.dtype == STRING else None
        validity = out.get(f"av:{a.name}")
        cols[a.name] = Column(f.dtype, fit(out[f"a:{a.name}"]),
                              None if validity is None else fit(validity),
                              dic)
    return Table(cols, valid_rows=out_valid)


# The fusion layer's counters are a named collector in the process metrics
# registry (telemetry/metrics.py), the program-bank precedent.
from ..telemetry import metrics as _metrics  # noqa: E402

_metrics.get_registry().register_collector("fusion", stats)
