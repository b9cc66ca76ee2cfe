"""SPMD distributed query execution over the device mesh.

The query-side product path for multi-chip execution (the build side is
parallel/distributed_build.py). The reference runs *every* plan distributed
because Spark is its engine; here eligible aggregation plans run SPMD over a
1-D mesh with XLA collectives (psum/pmin/pmax over ICI), and everything else
falls back to the single-device executor.

Supported plan shapes (checked structurally; any mismatch → fallback):

    Aggregate[global or grouped]                     (try_execute_aggregate)
      └─ chain of {Filter, Project, Join}*
           └─ Scan | IndexScan                       ← the sharded stream

    [Limit] [Sort] chain of {Filter, Project, Join}* (try_execute_plan —
      └─ Scan | IndexScan                     row-returning stream queries)

Execution model — mask-based streaming with static shapes throughout.
The per-device program launches as ONE mesh-partitioned ``jax.jit``
through ``parallel/sharding.device_view`` (NamedSharding + sharding
constraints — see that module) and registers in the serving ProgramBank
keyed on (stage fingerprint, shape-class vector, mesh signature):

- The leaf table is loaded once and row-sharded over the mesh
  (``pad_and_shard``; multi-file parquet scans shard file-aligned —
  each device's rows come from its own files, read through the parallel
  reader pool); a boolean *keep mask* rides along instead of physically
  filtering, so every shape stays static in the partitioned program.
- Filters AND into the mask; Projects re-evaluate live columns (the
  expression evaluator is shape-preserving and traces cleanly per device).
- Joins pick one of two strategies per stage, and cover every join type
  (inner, left/right/full outer, semi, anti — Spark distributes all of
  them, so falling back would concede the reference's coverage):
  * broadcast (m:1): the non-stream side is materialized, required unique
    on the key, key-sorted, replicated, and probed per device: by one
    gather from a direct-address position table when its integer keys
    are dense, else by a searchsorted. Multi-key joins probe a bit-packed
    composite built from the broadcast side's per-column value ranges
    (out-of-range stream values hit a sentinel that never matches). Left
    outer keeps unmatched stream rows with the right columns invalid;
    semi/anti broadcast the KEYS only (duplicates fine) and just mask the
    stream.
  * exchange (m:n): both sides are hash-routed over ICI with ONE
    lax.all_to_all each (value-stable key hash → owner device, the
    reference's shuffle join), then merge-joined locally into
    capacity-bounded output slots; on capacity overflow the program
    reports its exact needs and ONE right-sized recompile retries
    (2 in the rare skewed-send case) — never an open-ended
    escalation ladder on a backend where compiles are the risk.
    Multi-key joins route on the bit-packed composite. Because equal
    keys all meet on one device, local match status is global: left
    outer pads unmatched stream rows in place, right/full outer
    append each owner's unmatched right rows — no extra collective.
- Global aggregates psum/pmin/pmax partial contributions (one collective
  per partial).
- Grouped aggregates compute capacity-bounded per-device partials (local
  sort → segment ops into ``G`` slots). On real multi-chip meshes the
  partial groups then hash-route to owner devices with one all_to_all
  and combine there — the full two-phase shuffle-aggregate on device;
  the host receives disjoint final groups and only concatenates + orders
  them (owner capacity retries once with the exact reported need,
  hard-bounded by ``n_dev*G``). On single-host CPU meshes the exchange
  would run on the same silicon as the host merge, so the partials go
  straight to the host merge instead (_use_routed_merge;
  HST_SPMD_ROUTED_MERGE=on|off overrides). Local-partial overflow still
  falls back (with a telemetry event).
- Row-returning (non-aggregate) chains return each device's columns +
  mask; the host gathers valid rows and concatenates (Sort/Limit wrappers
  then run on the reduced result).

Null semantics match the single-device executor: filters keep
true-and-valid rows, inner-join null keys never match, aggregates skip
invalid values, and nullable group keys treat null as its own group
(null-first in the output order, the same encoding the single-device
path uses — executor._null_aware_keys).
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import kernels
from ..parallel.mesh import (DATA_AXIS, local_devices, make_mesh,
                             pad_and_shard, pad_and_shard_blocks)
from ..parallel.sharding import bank_program, device_view, mesh_signature
from ..plan import expr as E
from ..plan.nodes import (Aggregate, Filter, IndexScan, Join, LogicalPlan,
                          Project, Scan)
from ..schema import BOOL, DATE, FLOAT64, INT32, INT64, STRING
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace
from .columnar import Column, Table, dictionaries_equal, translate_codes
from .evaluator import eval_expr, eval_predicate_mask

# Max distinct groups per device shard for grouped aggregation. Beyond this
# the SPMD path falls back (correctness first; a group count comparable to
# the row count has no partial-aggregation win anyway).
MAX_LOCAL_GROUPS = 1 << 16

# Successful SPMD executions in this process (tests / dryrun assert the
# path is actually taken). These tallies (and LAST_CAP_ATTEMPTS below)
# are bumped by concurrent serving workers and asserted exact by tests,
# so every write happens under the lock — an unguarded += loses updates
# (HS301/HS302, scripts/analysis lock-discipline registry).
DISPATCH_COUNT = 0

# Distributed ORDER BY executions (range-partitioned sample sort).
SORT_DISPATCH_COUNT = 0

_COUNT_LOCK = threading.Lock()

# Per-device sample count for the distributed sort's splitter estimation.
_SORT_SAMPLES = 64


class _Unsupported(Exception):
    """Plan/dtype/shape not handled by the SPMD path — fall back."""


_DEVICE_DTYPE = {INT32: jnp.int32, INT64: jnp.int64, "float32": jnp.float32,
                 FLOAT64: jnp.float64, BOOL: jnp.bool_, DATE: jnp.int32,
                 STRING: jnp.int32}


# ---------------------------------------------------------------------------
# Plan linearization + column-need analysis.
# ---------------------------------------------------------------------------

def _linearize(plan: LogicalPlan):
    """Split the subtree under Aggregate into (leaf, bottom-up stage list).
    The sharded stream side of a Join is its *left* child (fact table
    left, dimension right — the DataFrame API convention)."""
    stages: List[Tuple[str, LogicalPlan]] = []
    node = plan
    while True:
        if isinstance(node, (Scan, IndexScan)):
            return node, list(reversed(stages))
        if isinstance(node, Filter):
            stages.append(("filter", node))
            node = node.child
        elif isinstance(node, Project):
            stages.append(("project", node))
            node = node.child
        elif isinstance(node, Join):
            stages.append(("join", node))
            node = node.left
        else:
            raise _Unsupported(node.node_name)


def _load_leaf(leaf, stages, needed) -> "Table":
    """Materialize the stream leaf, pruning the read when possible.

    Filter stages sitting DIRECTLY above the leaf (before any project or
    join stage) are necessary conditions on the raw leaf rows, so their
    pushable conjuncts can narrow the parquet read — the same IO
    optimization the single-device Filter-over-leaf branch applies; the
    later mask evaluation over the pruned rows is unchanged. For an
    IndexScan leaf, a leading-indexed-column constraint additionally
    bypasses the HBM cache (within-bucket sort makes row-group pruning
    sharp — executor._execute's policy).

    The returned table may be CLASS-PADDED (``valid_rows`` set):
    compacting here would compile one gather per distinct valid count,
    while the SPMD stream's keep mask absorbs the pad tail for free and
    class-stable shapes are exactly what lets the sharded programs bank
    (the r07 padding contract carried through r12's launcher)."""
    from . import executor as ex

    conds = []
    for kind, node in stages:
        if kind != "filter":
            break
        conds.append(node.condition)
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
                # Filter matched nothing: fall through to the cached full
                # read so the SPMD stream still runs (an all-false mask)
                # instead of a spurious single-device fallback.
        else:  # Scan: dotted struct leaves aren't physical columns there.
            pa_filter = pushable_filter(combined, leaf.schema,
                                        allow_nested=False)
            if pa_filter is not None:
                table = ex._execute_scan(leaf, needed, pa_filter)
                if table.num_rows > 0:
                    return table
    # Padded-pipeline read (NOT the compacting callback): the stream
    # shards the physical class-padded arrays and masks the tail.
    return ex._execute(leaf, needed)


def _normalized_join_pairs(join: Join) -> List[Tuple[str, str]]:
    pairs = E.extract_equi_join_keys(join.condition)
    if pairs is None:
        raise _Unsupported("non-equi join")
    left_names = set(join.left.schema.names)
    right_names = set(join.right.schema.names)
    norm = []
    for a, b in pairs:
        if a in left_names and b in right_names:
            norm.append((a, b))
        elif b in left_names and a in right_names:
            norm.append((b, a))
        else:
            raise _Unsupported("join keys do not split across sides")
    return norm


def _needed_per_stage(needed: Set[str], stages):
    """Top-down walk computing the leaf's needed column set, per join stage
    the non-stream side's needed set, and per project stage the *live*
    output names (the traced program evaluates only those — a dead project
    expr may reference columns that were pruned below it).

    ``right_used[i]`` is the subset of the right side's columns a stage
    above actually consumes — join KEYS appear in ``right_needed[i]``
    (the side must be materialized with them to compute routing codes)
    but ride the exchange as data only when used."""
    needed = set(needed)
    right_needed: Dict[int, Set[str]] = {}
    right_used: Dict[int, Set[str]] = {}
    project_live: Dict[int, frozenset] = {}
    for i in range(len(stages) - 1, -1, -1):
        kind, node = stages[i]
        if kind == "filter":
            needed = needed | set(node.condition.references)
        elif kind == "project":
            live = {e.name for e in node.exprs if e.name in needed}
            project_live[i] = frozenset(live)
            below: Set[str] = set()
            for e in node.exprs:
                if e.name in live:
                    below |= set(e.references)
            needed = below
        else:  # join
            pairs = _normalized_join_pairs(node)
            if node.join_type in ("semi", "anti"):
                # Existence probe: the right side contributes keys only
                # and no columns survive into the output (schema = left).
                right_needed[i] = {r for _, r in pairs}
                right_used[i] = set()
                needed = needed | {l for l, _ in pairs}
            else:
                rnames = set(node.right.schema.names)
                right_used[i] = {n for n in needed if n in rnames}
                right_needed[i] = right_used[i] | {r for _, r in pairs}
                needed = {n for n in needed if n not in rnames} | \
                    {l for l, _ in pairs}
    return needed, right_needed, right_used, project_live


# ---------------------------------------------------------------------------
# Join sides. Two strategies, chosen per join stage:
#   broadcast — small m:1 side replicated to every device, probed through
#     a position table or a searchsorted (the reference's broadcast join,
#     primitive 4);
#   exchange — both sides hash-routed over ICI with one all_to_all so equal
#     keys meet on one device, then merge-joined locally (the reference's
#     shuffle join, primitives 1+5). Handles m:n and big-big joins.
# ---------------------------------------------------------------------------

class _BroadcastSide:
    """A materialized, key-sorted, key-unique join side: ``keys`` ascending
    in the stream key's code space (null keys dropped — inner join),
    ``table`` row-aligned with ``keys``. ``pack`` is the multi-key
    composite spec: a tuple of (rmin, shift, sentinel) per key column —
    None for single-key joins. ``slot``/``kmin`` is the direct-address
    position table and its base key (kernels.position_table), or None
    where the probe searches ``keys``."""

    def __init__(self, keys: jax.Array, table: Table, pack=None,
                 slot=None, kmin=None):
        self.keys = keys
        self.table = table
        self.pack = pack
        self.slot = slot
        self.kmin = kmin

    @property
    def probe(self) -> str:
        return "search" if self.slot is None else "direct"


class _ExchangeSide:
    """An m:n join side sharded over the mesh for the bucket exchange.
    ``arrays``/``valid`` are row-sharded (pad_and_shard); ``key_dtype`` is
    the stream-code-space dtype used for value-stable routing hashes.
    ``stream_meta`` snapshots the STREAM side's per-column metadata at this
    stage (projects below the join may have created or redefined columns
    that the leaf col_meta doesn't know). ``pack`` is the multi-key
    composite spec (None for single-key): the routed "k" arrays hold the
    packed int64 composite, and every right column — keys included —
    additionally rides as data so outer-join appendix rows can surface
    their own key values."""

    def __init__(self, arrays: Dict[str, jax.Array], valid: jax.Array,
                 table_meta: Dict[str, Tuple[str, Optional[np.ndarray], bool]],
                 key_dtype: str,
                 stream_meta: Dict[str, Tuple[str, Optional[np.ndarray], bool]],
                 pack=None):
        self.arrays = arrays
        self.valid = valid
        self.table_meta = table_meta
        self.key_dtype = key_dtype
        self.stream_meta = stream_meta
        self.pack = pack


def _right_key_codes(right: Table, rkey: str, lcol: Column) -> jax.Array:
    """The right key column in the STREAM side's code space (strings are
    translated into the stream dictionary so codes compare equal iff the
    strings do)."""
    rc = right.column(rkey)
    if rc.dtype != lcol.dtype:
        raise _Unsupported("join key dtype mismatch")
    if rc.dtype == STRING and not dictionaries_equal(lcol.dictionary,
                                                     rc.dictionary):
        return translate_codes(lcol.dictionary, rc)
    return rc.data


def _drop_null_keys(right: Table, rkeys: List[str]):
    keep = None
    for rk in rkeys:
        v = right.column(rk).validity
        if v is not None:
            keep = v if keep is None else (keep & v)
    if keep is not None:  # inner join: null keys never match.
        return right.filter(keep), keep
    return right, None


def _pack_codes(codes):
    """Bit-pack multiple key-code arrays into one int64 composite (None
    pack for single-key). Each key column is offset into [0, range) from
    the RIGHT side's own min/max and packed into disjoint bit fields. A +1
    sentinel per field encodes "stream value outside the right side's
    range" — it can never equal a packed right key, so composite equality
    ⇔ per-column equality, exactly."""
    if len(codes) == 1:
        return codes[0], None
    pack = []
    shift = 0
    packed = None
    for c in codes:
        c64 = c.astype(jnp.int64)
        if c64.shape[0] == 0:
            rmin, rmax = 0, 0
        else:
            rmin = int(jnp.min(c64))
            rmax = int(jnp.max(c64))
        span = rmax - rmin + 2  # +1 for the out-of-range sentinel
        bits = max(int(span - 1).bit_length(), 1)
        pack.append((rmin, shift, span - 1))
        packed = (c64 - rmin) << shift if packed is None else \
            packed | ((c64 - rmin) << shift)
        shift += bits
        if shift > 62:
            raise _Unsupported("multi-key composite exceeds 62 bits")
    return packed, tuple(pack)


def _prepare_broadcast(right: Table, pairs, tiny: Dict[str, Column],
                       stream_rows: int,
                       keys_only: bool = False) -> _BroadcastSide:
    """``keys_only`` (semi/anti probes) skips the m:1 uniqueness demand —
    duplicate keys are harmless to an existence probe — and ships no data
    columns at all.

    Integer keys (dates, string codes and packed composites included)
    whose span is dense get a direct-address position table, so the probe
    is one gather where a searchsorted takes log2(keys) dependent rounds.
    The table is replicated like the side itself; it is built when its
    int32 slots take no more bytes than the stream's probe-key column on
    each device (``stream_rows`` per device at this stage). Its length
    is the key span rounded up to a power of two: the span moves with
    every change of the data, and an exact length would compile the
    probing program anew each time."""
    right, _ = _drop_null_keys(right, [r for _, r in pairs])
    codes = [_right_key_codes(right, rname, tiny[lname])
             for lname, rname in pairs]
    keys, pack = _pack_codes(codes)
    order = kernels.lex_sort_indices([keys])
    keys = jnp.take(keys, order)
    table = Table({}) if keys_only else right.take(order)
    n_r = int(keys.shape[0])
    dense = n_r > 0 and jnp.issubdtype(keys.dtype, jnp.integer)
    # One host sync for the m:1 requirement (the side unique on the key)
    # and the key range.
    ask = {}
    if not keys_only and n_r > 1:
        ask["dup"] = jnp.any(keys[1:] == keys[:-1])
    if dense:
        ask["lo"], ask["hi"] = keys[0], keys[-1]
    got = jax.device_get(ask) if ask else {}
    if got.get("dup"):
        raise _Unsupported("broadcast join side has duplicate keys")
    if dense:
        length = _round_up_pow2(int(got["hi"]) - int(got["lo"]) + 1)
        if 4 * length <= keys.dtype.itemsize * stream_rows:
            with _trace.span(SN.JOIN_PROBE_TABLE, keys=n_r, slots=length):
                slot, kmin = kernels.position_table(
                    keys, length=length, unique=not keys_only)
            return _BroadcastSide(keys, table, pack, slot, kmin)
    return _BroadcastSide(keys, table, pack)


def _prepare_exchange(right: Table, pairs, tiny: Dict[str, Column],
                      mesh: Mesh, used: Set[str],
                      keep_null_keys: bool) -> _ExchangeSide:
    """Shard an m:n join side over the mesh for the all-to-all route.
    Multi-key joins route on the bit-packed composite (the same trick the
    broadcast side uses, VERDICT r3 #7) — both sides hash the composite,
    so equal key TUPLES meet on one device.

    ``used`` gates the data payload: join keys ride only the routing "k"
    array unless a stage above consumes the column. ``keep_null_keys``
    (right/full outer) keeps null-key rows in the route — they match
    nothing, but the preserving side must still emit them (the single-
    device executor's _execute_outer_join does); a "kv" flag rides along
    so the merge can exclude them from matching."""
    key_validity = None
    if keep_null_keys:
        for _, rk in pairs:
            v = right.column(rk).validity
            if v is not None:
                key_validity = v if key_validity is None \
                    else (key_validity & v)
    else:
        right, _ = _drop_null_keys(right, [r for _, r in pairs])
    codes = [_right_key_codes(right, rname, tiny[lname])
             for lname, rname in pairs]
    if right.num_rows == 0:
        raise _Unsupported("empty exchange side")
    if key_validity is not None and len(pairs) > 1:
        # Null slots hold arbitrary fill — pin them to each column's valid
        # min so the composite's bit budget reflects real values only.
        pinned = []
        for c in codes:
            vmin = jnp.min(jnp.where(key_validity, c,
                                     _max_sentinel(c.dtype)))
            vmin = jnp.where(jnp.any(key_validity), vmin,
                             jnp.zeros((), c.dtype))
            pinned.append(jnp.where(key_validity, c, vmin))
        codes = pinned
    keys, pack = _pack_codes(codes)
    arrays: Dict[str, jax.Array] = {"k": keys}
    if key_validity is not None:
        arrays["kv"] = key_validity
    rkeys = {r for _, r in pairs}
    # Key columns ride as data only when some stage consumes them AND the
    # program cannot rebuild them for free from the stream side: single-
    # key non-preserve-right joins reconstruct the right key from the
    # stream key (equal by definition on matches, null on padding), so
    # only composites (unpackable) and right/full (appendix rows carry
    # their OWN key values) pay the duplicate payload.
    carry_keys = pack is not None or keep_null_keys
    meta: Dict[str, Tuple[str, Optional[np.ndarray], bool]] = {}
    for n in right.names:
        if n in rkeys and not (n in used and carry_keys):
            continue
        rc = right.column(n)
        arrays[f"d:{n}"] = rc.data
        if rc.validity is not None:
            arrays[f"v:{n}"] = rc.validity
        meta[n] = (rc.dtype, rc.dictionary, rc.validity is not None)
    from .shapes import padded_length
    arrays, valid = pad_and_shard(mesh, arrays, right.num_rows,
                                  pad_rows=padded_length(right.num_rows))
    stream_meta = {n: (c.dtype, c.dictionary, c.validity is not None)
                   for n, c in tiny.items()}
    key_dtype = INT64 if pack is not None else tiny[pairs[0][0]].dtype
    return _ExchangeSide(arrays, valid, meta, key_dtype, stream_meta, pack)


# ---------------------------------------------------------------------------
# Aggregate specs: per-device partials + host finalization.
# ---------------------------------------------------------------------------

def _strip_alias(e: E.Expr):
    while isinstance(e, E.Alias):
        e = e.child
    return e


def _min_sentinel(dtype):
    return jnp.asarray(
        jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating)
        else jnp.iinfo(dtype).min, dtype)


def _max_sentinel(dtype):
    return jnp.asarray(
        jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating)
        else jnp.iinfo(dtype).max, dtype)


class _AggSpec:
    """One aggregate: how to fold per-device partials and finalize merged
    partials on host. Output dtypes mirror executor._eval_agg exactly."""

    def __init__(self, name: str, kind: str, child: Optional[E.Expr],
                 out_dtype: str, dictionary=None):
        self.name = name
        self.kind = kind  # count | sum | avg | min | max
        self.child = child
        self.out_dtype = out_dtype
        self.dictionary = dictionary

    @staticmethod
    def build(agg: E.Expr, probe: Callable[[E.Expr], Column]) -> "_AggSpec":
        inner = _strip_alias(agg)
        name = agg.name
        if isinstance(inner, E.Count):
            return _AggSpec(name, "count", inner.child, INT64)
        if not isinstance(inner, (E.Sum, E.Avg, E.Min, E.Max)):
            raise _Unsupported(f"agg {inner!r}")
        c = probe(inner.child)
        if isinstance(inner, (E.Min, E.Max)):
            kind = "min" if isinstance(inner, E.Min) else "max"
            return _AggSpec(name, kind, inner.child, c.dtype, c.dictionary)
        if c.dtype == STRING:
            raise _Unsupported("sum/avg over string column")
        if isinstance(inner, E.Sum):
            is_f = c.dtype in (FLOAT64, "float32")
            return _AggSpec(name, "sum", inner.child,
                            FLOAT64 if is_f else INT64)
        return _AggSpec(name, "avg", inner.child, FLOAT64)

    def partial_keys(self) -> List[str]:
        if self.kind == "count":
            return ["count"]
        if self.kind in ("sum", "avg"):
            return ["sum", "count"]
        return [self.kind, "count"]

    # ---- per-device (traced); fold maps per-row arrays → partials ----

    def partials(self, table: Table, mask, fold) -> Dict[str, jax.Array]:
        if self.kind == "count":
            if self.child is None:
                v = mask
            else:
                c = eval_expr(table, self.child)
                v = mask if c.validity is None else (mask & c.validity)
            return {"count": fold["sum"](v.astype(jnp.int64))}
        c = eval_expr(table, self.child)
        valid = mask if c.validity is None else (mask & c.validity)
        cnt = fold["sum"](valid.astype(jnp.int64))
        if self.kind in ("sum", "avg"):
            acc = c.data.astype(jnp.float64) \
                if jnp.issubdtype(c.data.dtype, jnp.floating) \
                else c.data.astype(jnp.int64)
            return {"sum": fold["sum"](jnp.where(valid, acc, 0)),
                    "count": cnt}
        if self.kind == "min":
            vals = jnp.where(valid, c.data, _max_sentinel(c.data.dtype))
            return {"min": fold["min"](vals), "count": cnt}
        vals = jnp.where(valid, c.data, _min_sentinel(c.data.dtype))
        return {"max": fold["max"](vals), "count": cnt}

    # ---- host finalization over merged numpy partials ----

    def finalize(self, merged: Dict[str, np.ndarray],
                 nullable_inputs: bool) -> Column:
        cnt = merged["count"]
        if self.kind == "count":
            return Column(INT64, jnp.asarray(cnt.astype(np.int64)))
        # Parity with _eval_agg: output validity only when the input column
        # was nullable (SQL: empty-of-valid group aggregates to NULL).
        validity = jnp.asarray(cnt > 0) if nullable_inputs else None
        if self.kind == "sum":
            dt = np.float64 if self.out_dtype == FLOAT64 else np.int64
            return Column(self.out_dtype,
                          jnp.asarray(merged["sum"].astype(dt)), validity)
        if self.kind == "avg":
            s = merged["sum"].astype(np.float64)
            return Column(FLOAT64, jnp.asarray(s / np.maximum(cnt, 1)),
                          validity)
        return Column(self.out_dtype, jnp.asarray(merged[self.kind]),
                      validity, self.dictionary)


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------

def _device_count(session=None) -> int:
    """Devices the dispatch mesh will span (tests shrink this to exercise
    the 1-device fused path on a multi-device host; the
    ``distributed.mesh.maxDevices`` knob caps it, 0 = all local)."""
    cap = session.hs_conf.distributed_mesh_max_devices() \
        if session is not None else 0
    return len(local_devices(cap))


def _spmd_eligible(session) -> bool:
    if session is None:
        return False
    if not session.hs_conf.distributed_enabled():
        return False
    from ..serving import batcher
    if batcher.active_sweep() is not None:
        # A literal-sweep batch already collapses its members into ONE
        # vmapped invocation over shared scans (serving/batcher.py) —
        # the sweep kernel lives in the single-device padded pipeline,
        # and distributing each member individually would both defeat
        # the batching win and skip the shared-scan accounting.
        return False
    if _device_count(session) >= 2:
        return True
    # ONE device: the "SPMD" program degenerates to a single fused jit
    # program (collectives over a 1-device mesh are identity, and XLA
    # removes them). That still matters on an accelerator, where the
    # staged executor pays a host↔device round trip per operator while the
    # fused program pays ~one. On CPU the "device" shares the silicon with
    # the host, so fusing buys nothing and costs compiles; "auto" therefore
    # keys on the backend.
    mode = session.hs_conf.distributed_single_device()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return jax.default_backend() not in ("cpu",)


def _stream_leaf_rows(root) -> Optional[int]:
    """Row count of the stream leaf from parquet METADATA only (no read),
    or None when unknowable (non-parquet, structural mismatch — let the
    caller proceed/fail for its own reason)."""
    from .columnar import parquet_row_counts

    try:
        leaf, _ = _linearize(root)
    except _Unsupported:
        return None
    if isinstance(leaf, IndexScan):
        # Index leaves materialize fully (index content PLUS any hybrid
        # appended files).
        try:
            return sum(parquet_row_counts(
                list(leaf.index_entry.content.files)
                + list(leaf.appended_files)))
        except Exception:
            return None
    if not isinstance(leaf, Scan):
        return None
    relation = leaf.relation
    fmt = getattr(relation, "data_file_format", relation.file_format)
    if fmt != "parquet":
        return None
    try:
        return sum(parquet_row_counts(relation.all_files()))
    except Exception:
        return None


def _leaf_within_budget(root, session) -> bool:
    """False when the stream leaf exceeds the device-footprint budget —
    the SPMD path materializes the leaf before sharding, so oversized
    sources must go to the chunked single-device path instead (the two
    compose once the chunked reader learns to feed shards directly)."""
    total = _stream_leaf_rows(root)
    return total is None or total <= session.hs_conf.max_chunk_rows()


def _passes_min_rows(root, session) -> bool:
    """The distributed COST GATE: streams whose leaf holds fewer rows
    than ``distributed.minStreamRows`` stay single-device — an N-device
    program over a few hundred rows pays compile + collective overhead
    for zero scaling win (and on the virtual test mesh it would tax the
    whole suite with mesh compiles). Unknown row counts pass (the
    structural checks decide). Observable like every other fallback."""
    min_rows = session.hs_conf.distributed_min_stream_rows()
    if min_rows <= 0:
        return True
    rows = _stream_leaf_rows(root)
    if rows is None or rows >= min_rows:
        return True
    from ..telemetry.logging import emit_distributed_fallback
    emit_distributed_fallback(
        session, "spmd_query",
        f"stream leaf {rows} rows below distributed.minStreamRows "
        f"{min_rows}")
    return False


def try_execute_aggregate(plan: Aggregate, session,
                          executor: Callable) -> Optional[Table]:
    """Execute an Aggregate subtree SPMD over the mesh, or return None to
    fall back. ``executor(plan, needed)`` is the single-device recursive
    executor, used to materialize the scan leaf and join sides."""
    with _trace.span(SN.SPMD_GATE):
        if not _spmd_eligible(session):
            return None
        if not _passes_min_rows(plan.child, session):
            return None
        if not _leaf_within_budget(plan.child, session):
            from ..telemetry.logging import emit_distributed_fallback
            emit_distributed_fallback(session, "spmd_query",
                                      "leaf exceeds device chunk budget")
            return None
    try:
        return _run(plan, executor, session)
    except _Unsupported as e:
        from ..telemetry.logging import emit_distributed_fallback
        emit_distributed_fallback(session, "spmd_query", str(e))
        return None


def try_execute_plan(plan, session, executor: Callable) -> Optional[Table]:
    """Row-returning distributed execution for non-aggregate roots: a
    {Filter, Project, Join}* chain over a scan (optionally under Sort /
    Limit wrappers) runs SPMD; valid rows are gathered per device and
    concatenated on host, then the wrappers run single-device (their input
    is already reduced). Returns None to fall back."""
    from ..plan.nodes import Limit, Sort

    with _trace.span(SN.SPMD_GATE):
        if not _spmd_eligible(session):
            return None
        wrappers = []
        node = plan
        while isinstance(node, (Sort, Limit)):
            wrappers.append(node)
            node = node.child
        if isinstance(node, Aggregate):
            return None  # aggregates dispatch inside the executor
        if isinstance(node, (Scan, IndexScan)) and not (
                wrappers and isinstance(wrappers[-1], Sort)
                and _use_spmd_sort()):
            return None  # a bare scan has no distributed work — unless a
            # Sort sits above it (the distributed sample sort IS the work)
        try:
            _linearize(node)  # raises _Unsupported on non-chain shapes
        except _Unsupported:
            return None
        if not _passes_min_rows(node, session):
            return None
        if not _leaf_within_budget(node, session):
            from ..telemetry.logging import emit_distributed_fallback
            emit_distributed_fallback(session, "spmd_query",
                                      "leaf exceeds device chunk budget")
            return None
    # Distributed ORDER BY: the innermost Sort runs ON the mesh as a
    # range-partitioned sample sort, so the host gather receives sorted
    # device ranges instead of unsorted rows (VERDICT r5 #4).
    sort_orders: Tuple = ()
    if wrappers and isinstance(wrappers[-1], Sort) and _use_spmd_sort():
        sort_orders = tuple(wrappers[-1].orders)
        wrappers = wrappers[:-1]
    try:
        table = _run_stream(node, executor, sort_orders, session)
    except _Unsupported as e:
        from ..telemetry.logging import emit_distributed_fallback
        emit_distributed_fallback(session, "spmd_query", str(e))
        return None
    # Wrappers (outermost first in `wrappers`): apply innermost-out.
    from . import executor as ex
    for w in reversed(wrappers):
        if isinstance(w, Sort):
            table = ex._execute_sort(w, table)
        else:
            table = table.slice(0, min(w.n, table.num_rows))
    return table


def _use_spmd_sort() -> bool:
    """Backend cost decision for the distributed ORDER BY, mirroring
    _use_routed_merge: on a single-host CPU mesh the sample-sort
    collectives run on the silicon the host sort would use, so the host
    sort wins; on real multi-chip the sort scales with devices and the
    exchange rides ICI. HST_SPMD_SORT=on|off overrides (tests and the
    multi-chip dryrun force it on)."""
    mode = os.environ.get("HST_SPMD_SORT", "auto")
    if mode in ("on", "off"):
        return mode == "on"
    return jax.devices()[0].platform != "cpu"


def _dict_fingerprint(dic: Optional[np.ndarray]):
    if dic is None:
        return None
    # Dictionaries are trace-time constants (translate tables, literal
    # bounds); they must participate in the compile-cache key by *content*
    # (not a hash of the content) so __eq__ compares real values and a
    # hash collision can never alias two distinct compiled programs.
    return tuple(dic.tolist())


class _Prepared:
    """Everything _spmd_program needs, prepared once per execution: the
    sharded stream, replicated broadcast arrays, sharded exchange arrays,
    join descriptors, per-stage metadata, and the final (post-stage) column
    metadata for probing aggregate dtypes / rebuilding host tables."""

    def __init__(self, mesh, n_dev, sharded, valid, bcast, xch, stages,
                 joins, col_meta, final_meta, shard_rows, out_rows,
                 project_live, file_aligned=False):
        self.mesh = mesh
        self.n_dev = n_dev
        self.sharded = sharded
        self.valid = valid
        self.bcast = bcast
        self.xch = xch
        self.stages = stages
        self.joins = joins
        self.col_meta = col_meta
        self.final_meta = final_meta
        self.shard_rows = shard_rows
        self.out_rows = out_rows  # per-device rows after the last stage
        self.project_live = project_live  # stage idx -> live output names
        self.file_aligned = file_aligned  # leaf sharded on file boundaries


def _file_aligned_bounds(leaf, leaf_table, n_dev: int):
    """Row offsets assigning whole files to devices, or None. Only for
    plain multi-file parquet Scan leaves whose materialized row count
    matches the file metadata exactly (no pruned read, no class padding)
    — then splitting the already-read arrays at file boundaries gives
    every device rows from its own files at zero extra IO (the host read
    itself fanned per-file through the parallel reader pool). Any
    monotonic bounds are CORRECT (order preserved, padding masked);
    alignment buys locality, not semantics."""
    from .columnar import parquet_row_counts

    if not isinstance(leaf, Scan):
        return None
    relation = leaf.relation
    fmt = getattr(relation, "data_file_format", relation.file_format)
    if fmt != "parquet":
        return None
    try:
        files = list(relation.all_files())
        counts = parquet_row_counts(files)
    except Exception:
        return None
    if len(counts) < 2 or sum(counts) != leaf_table.num_rows:
        return None
    total = sum(counts)
    bounds = [0]
    acc = 0
    i = 0
    for d in range(1, n_dev):
        target = (d * total) // n_dev
        while i < len(counts) and acc + counts[i] <= target:
            acc += counts[i]
            i += 1
        bounds.append(acc)
    bounds.append(total)
    # Skew guard: every shard pads to the LARGEST block, so a lopsided
    # file layout (one giant file among small ones) would inflate device
    # memory toward n_dev x the data and serialize the real work onto
    # few devices. At 2x the even shard and beyond, locality stops
    # paying for the padding — fall back to the even row split. (Below
    # that the ratio is ordinary file-granularity quantization: e.g. 5
    # equal files over 8 devices necessarily hands some device a whole
    # file, 1.6x the even shard.)
    largest = max(bounds[d + 1] - bounds[d] for d in range(n_dev))
    if largest >= -(-total // n_dev) * 2:
        return None
    return bounds


def _sharded_blocks(mesh, leaf, stream_arrays, bounds, shard_rows):
    """File-aligned device sharding through the tiered buffer pool: the
    per-device sharded blocks are cached keyed by (leaf file signature,
    array names, block bounds, padded shard rows, mesh signature) so a
    repeat scan of unchanged files re-serves the SAME device buffers
    with zero host→device transfers. Entries are device-only (opaque
    sharded layouts never demote — evicted by dropping), and a
    different mesh never shares (its buffers live on other devices)."""
    from ..parallel.sharding import mesh_signature
    from . import buffer_pool as _bp

    key = None
    if _bp.enabled():
        try:
            files = list(leaf.relation.all_files())
        except Exception:
            files = None
        if files:
            key = _bp.blocks_key(files, sorted(stream_arrays), bounds,
                                 shard_rows, mesh_signature(mesh))
        if key is not None:
            cached = _bp.get_pool().get(key)
            if cached is not None:
                return cached
    sharded, valid = pad_and_shard_blocks(mesh, stream_arrays, bounds,
                                          shard_rows=shard_rows)
    if key is not None:
        nbytes = sum(int(a.nbytes) for a in sharded.values()) \
            + int(valid.nbytes)
        _bp.get_pool().put(key, (sharded, valid), nbytes=nbytes,
                           device_only=True)
    return sharded, valid


def _prepare(root, executor, caps: Dict[int, Tuple[int, int]],
             session=None) -> _Prepared:
    """Walk the stage chain preparing each join side. The walk runs over
    zero-length columns (the evaluator propagates dtype/dictionary/
    nullability exactly as the traced per-device program will), so join
    prep sees the stream key's *post-stage* metadata — a Project below a
    Join that redefines the key name feeds the join side the same
    dtype/dictionary the traced probe will use, never stale leaf meta.

    ``caps`` carries per-exchange-join capacities (send cap, output slots)
    from the retry loop; empty on the first attempt (defaults computed
    here)."""
    leaf, stages = _linearize(root)
    out_needed = set(root.schema.names)
    leaf_needed, right_needed, right_used, project_live = _needed_per_stage(
        out_needed, stages)

    leaf_table = _load_leaf(leaf, stages,
                            set(leaf_needed) if leaf_needed else None)
    if leaf_table.num_rows == 0:
        raise _Unsupported("empty stream")

    mesh = make_mesh(jax.devices()[:_device_count(session)])
    n_dev = mesh.devices.size

    stream_arrays: Dict[str, jax.Array] = {}
    col_meta: Dict[str, Tuple[str, Optional[np.ndarray], bool]] = {}
    for name in leaf_table.names:
        c = leaf_table.column(name)
        stream_arrays[f"d:{name}"] = c.data
        if c.validity is not None:
            stream_arrays[f"v:{name}"] = c.validity
        col_meta[name] = (c.dtype, c.dictionary, c.validity is not None)
    # Stream sharding keeps the r07 static-shape contract: the leaf pads
    # to its geometric LENGTH CLASS (shapes.padded_length under the
    # executor's active params) before the device split, so repeated
    # executions over different-length sources within one class hit ONE
    # compiled mesh program in the bank — the valid mask keeps results
    # byte-identical.
    from .shapes import padded_length
    bounds = None
    if n_dev > 1 and session is not None \
            and session.hs_conf.distributed_mesh_file_aligned_scan():
        bounds = _file_aligned_bounds(leaf, leaf_table, n_dev)
    if bounds is not None:
        max_block = max(bounds[i + 1] - bounds[i]
                        for i in range(len(bounds) - 1))
        sharded, valid = _sharded_blocks(
            mesh, leaf, stream_arrays, bounds,
            padded_length(max_block))
    else:
        sharded, valid = pad_and_shard(
            mesh, stream_arrays, leaf_table.num_rows,
            pad_rows=padded_length(leaf_table.num_rows))
    shard_rows = next(iter(sharded.values())).shape[0] // n_dev
    out_rows = shard_rows

    joins: Dict[int, Tuple] = {}
    bcast_arrays: Dict[str, jax.Array] = {}
    xch_arrays: Dict[str, jax.Array] = {}
    tiny = {n: Column(dt, jnp.zeros(0, _DEVICE_DTYPE[dt]),
                      jnp.zeros(0, jnp.bool_) if nul else None, dic)
            for n, (dt, dic, nul) in col_meta.items()}
    for i, (kind, node) in enumerate(stages):
        if kind == "filter":
            continue
        if kind == "project":
            t = Table(tiny)
            live = project_live.get(i, frozenset())
            tiny = {e.name: eval_expr(t, e) for e in node.exprs
                    if e.name in live}
            continue
        pairs = _normalized_join_pairs(node)
        jt = node.join_type
        for lname, _ in pairs:
            if lname not in tiny:
                raise _Unsupported(f"unknown stream join key {lname}")
        right_table = executor(node.right, right_needed[i])
        side = None
        if jt in ("semi", "anti"):
            # Existence probe: keys-only broadcast (duplicates fine, no
            # data columns, no schema change) — the classic broadcast
            # semi join, and the SPMD home of SQL [NOT] IN / EXISTS.
            # An _Unsupported here (e.g. composite bit overflow) falls
            # back to single-device — never to the exchange, which has
            # no existence-probe mode.
            side = _prepare_broadcast(right_table, pairs, tiny, out_rows,
                                      keys_only=True)
        elif jt in ("inner", "left"):
            # m:1 probe; left outer keeps unmatched stream rows with the
            # right columns invalid instead of masking them out.
            try:
                side = _prepare_broadcast(right_table, pairs, tiny, out_rows)
            except _Unsupported:
                side = None
        if side is not None:
            joins[i] = ("b", pairs, side, jt)
            if side.slot is None:
                bcast_arrays[f"k:{i}"] = side.keys
            else:
                bcast_arrays[f"s:{i}"] = side.slot
                bcast_arrays[f"m:{i}"] = side.kmin
            for n in side.table.names:  # empty for keys_only sides
                rc = side.table.column(n)
                if n not in {r for _, r in pairs}:
                    bcast_arrays[f"b:{i}:{n}"] = rc.data
                    if rc.validity is not None:
                        bcast_arrays[f"bv:{i}:{n}"] = rc.validity
            if jt in ("semi", "anti"):
                continue
        if side is None:
            # m:n (duplicate keys) and right/full outer → hash-route both
            # sides over ICI and merge-join locally: the reference's
            # shuffle join. Right/full need the exchange because only
            # there is a right row owned by exactly ONE device (a
            # replicated broadcast side would emit its unmatched rows
            # once per device).
            side = _prepare_exchange(right_table, pairs, tiny, mesh,
                                     right_used[i],
                                     keep_null_keys=jt in ("right", "full"))
            if i not in caps:
                r_shard = next(iter(side.arrays.values())).shape[0] // n_dev
                cap = min(2 * max(out_rows, r_shard) // n_dev + 1,
                          max(out_rows, r_shard))
                k_out = 2 * max(out_rows, r_shard)
                if jt in ("left", "full"):
                    k_out += out_rows  # every stream row may emit alone
                if jt in ("right", "full"):
                    k_out += 2 * r_shard  # plus the unmatched-right tail
                caps[i] = (cap, k_out)
            joins[i] = ("x", pairs, side, jt)
            for name, arr in side.arrays.items():
                xch_arrays[f"x:{i}:{name}"] = arr
            xch_arrays[f"x:{i}:__valid"] = side.valid
            out_rows = caps[i][1]
        # Post-join stream metadata: non-key right columns appear; matched
        # rows' right key values equal the left key's. Outer joins make
        # the null-padded side's columns nullable (nodes.Join.schema).
        if jt in ("right", "full"):
            # Meta comes from the tiny column itself, NOT col_meta: a
            # Project below this join may have created/renamed columns
            # col_meta never saw (KeyError here would escape the
            # _Unsupported fallback net as a crash).
            for n, c in list(tiny.items()):
                col_meta[n] = (c.dtype, c.dictionary, True)
                tiny[n] = Column(c.dtype,
                                 jnp.zeros(0, _DEVICE_DTYPE[c.dtype]),
                                 jnp.zeros(0, jnp.bool_), c.dictionary)
        rnames = {r for _, r in pairs}
        side_meta = side.table_meta if isinstance(side, _ExchangeSide) else \
            {n: (side.table.column(n).dtype, side.table.column(n).dictionary,
                 side.table.column(n).validity is not None)
             for n in side.table.names}
        for n, (dt, dic, nul) in side_meta.items():
            if jt in ("left", "full"):
                nul = True
            if n not in rnames:
                tiny[n] = Column(dt, jnp.zeros(0, _DEVICE_DTYPE[dt]),
                                 jnp.zeros(0, jnp.bool_) if nul else None,
                                 dic)
            col_meta[n] = (dt, dic, nul)
        for lname, rname in pairs:
            if rname in tiny:
                continue
            # Left/full outer: the right key column is null on the
            # unmatched-left padding rows, so it turns nullable even
            # when the source key is not. The exchange path carries
            # the right key column as data (its OWN dictionary) exactly
            # when a stage above consumes it (right_used); the broadcast
            # path rebuilds it from the stream key whenever the join
            # schema exposes it.
            if isinstance(side, _ExchangeSide) and rname in side.table_meta:
                dt, dic, nul0 = side.table_meta[rname]
                nul = nul0 or jt in ("left", "full")
            elif isinstance(side, _ExchangeSide):
                # Key rides no data: the program rebuilds it from the
                # stream key (single-key, non-preserve-right only).
                if side.pack is not None or jt in ("right", "full") \
                        or rname not in node.schema.names:
                    continue
                lc = tiny[pairs[0][0]]
                dt, dic = lc.dtype, lc.dictionary
                nul = lc.validity is not None or jt in ("left", "full")
            else:
                if rname not in node.schema.names:
                    continue
                lc = tiny[lname]
                dt, dic = lc.dtype, lc.dictionary
                nul = lc.validity is not None or jt in ("left", "full")
            tiny[rname] = Column(
                dt, jnp.zeros(0, _DEVICE_DTYPE[dt]),
                jnp.zeros(0, jnp.bool_) if nul else None, dic)
            col_meta[rname] = (dt, dic, nul)
    final_meta = {n: (c.dtype, c.dictionary, c.validity is not None)
                  for n, c in tiny.items()}
    return _Prepared(mesh, n_dev, sharded, valid, bcast_arrays, xch_arrays,
                     stages, joins, col_meta, final_meta, shard_rows,
                     out_rows, project_live,
                     file_aligned=bounds is not None)


def _emit_spmd_events(session, mode: str, prep: "_Prepared", caps,
                      attempts: int) -> None:
    """Observability per successful dispatch: one ShardedExecutionEvent with
    the mesh identity, the chosen PartitionSpecs, and the compiled
    program's HLO collective counts, plus one SpmdExchangeEvent per join
    stage (strategy, capacities) and one for the sort's range exchange.
    Event emission must never fail an execution."""
    if session is None:
        return
    try:
        from ..telemetry.events import SpmdExchangeEvent, ShardedExecutionEvent
        from ..telemetry.logging import NoOpEventLogger, get_logger
        logger = get_logger(session.hs_conf.event_logger_class())
        if isinstance(logger, NoOpEventLogger):
            return  # skip event (and lazy HLO-count) work entirely
        sig = mesh_signature(prep.mesh)
        out_specs = {"stream": f"rows:P({DATA_AXIS}) flags:P()",
                     "sort": f"rows:P({DATA_AXIS}) flags:P()",
                     "grouped-agg": f"partials:P({DATA_AXIS}) flags:P()",
                     "global-agg": "partials:P()"}[mode]
        logger.log_event(ShardedExecutionEvent(
            message=f"spmd {mode} over {prep.n_dev}-device mesh",
            mode=mode, mesh_axes=list(sig[0]), mesh_shape=list(sig[1]),
            mesh_platform=sig[2], shard_rows=prep.shard_rows,
            file_aligned_scan=prep.file_aligned,
            in_specs=f"stream:P({DATA_AXIS}) bcast:P() xch:P({DATA_AXIS})",
            out_specs=out_specs,
            collectives=last_collectives(), cap_attempts=attempts))
        for i in sorted(prep.joins):
            jkind, _pairs, _side, jt = prep.joins[i]
            cap, k_out = caps.get(i, (0, 0))
            logger.log_event(SpmdExchangeEvent(
                message=f"stage {i} {jt} join via "
                        + ("bucket exchange" if jkind == "x"
                           else "broadcast"),
                stage=i, join_type=jt,
                strategy="exchange" if jkind == "x" else "broadcast",
                capacity=cap, output_slots=k_out,
                all_to_all=2 if jkind == "x" else 0))
        if mode == "sort":
            cap, _ = caps.get(-1, (0, 0))
            logger.log_event(SpmdExchangeEvent(
                message="distributed sort range exchange", stage=-1,
                join_type="", strategy="sort-route", capacity=cap,
                output_slots=0, all_to_all=1))
    except Exception:
        pass  # observability must never fail an execution


# Exchange-capacity retries PER EXCHANGE JOIN: each retry recompiles with
# the EXACT needs the failed program reported (see _escalate_on_overflow),
# so one overflowing join needs 1 retry (2 with a skewed send). Chained
# exchange joins can discover needs one at a time — an upstream join's
# clamped output hides the downstream join's true input — so the budget
# scales with the join count instead of being a flat constant.
_MAX_CAP_RETRIES = 2

# Capacity attempts of the most recent _run/_run_stream (1 = first program
# fit). Tests pin the one-recompile contract with this. LAST-DISPATCH
# semantics only: concurrent queries overwrite each other here, so the
# per-query spans/events carry their own local attempt counts instead.
LAST_CAP_ATTEMPTS = 0


def _out_rows(prep: _Prepared, caps: Dict[int, Tuple[int, int]]) -> int:
    """Per-device rows after the last stage under the CURRENT caps (the
    last exchange join's output slots, or the stream shard size)."""
    rows = prep.shard_rows
    for i in sorted(i for i, j in prep.joins.items() if j[0] == "x"):
        rows = caps[i][1]
    return rows


def _record_join_actuals(session, prep: "_Prepared", out) -> None:
    """Write the SPMD program's observed inner-join output rows (the
    psum'd ``jrows:`` outputs) to the same session store the
    single-device executor uses (serving/context.record_join_actual) —
    the join-reorder q-error pairing works on the distributed path too,
    so its instrumentation no longer pins ``distributed.enabled=false``."""
    from ..serving import context as qctx
    ctx = qctx.active_context()
    for i, (kind, node) in enumerate(prep.stages):
        key = f"jrows:{i}"
        if kind != "join" or key not in out:
            continue
        rows = int(_fetch("spmd.join_rows", out[key]))
        akey = qctx.join_actual_key(node.condition, node.left, node.right)
        if ctx is not None:
            ctx.record_join_actual(akey, rows)
        elif session is not None:
            qctx.record_join_actual(session, akey, rows)


def _run(plan: Aggregate, executor, session=None) -> Table:
    """Dispatch wrapper: one ``spmd.dispatch`` span per mesh execution
    (capacity-escalation retries stay inside the one span — they are one
    dispatch from the query's point of view). The deadline check and the
    fault point sit here, BEFORE any mesh work: an expired query never
    pays a dispatch, and an injected dispatch fault propagates to the
    executor's SPMD->single-device degradation ladder."""
    from ..robustness import fault_names as _fltn
    from ..robustness import faults as _faults
    from ..serving.context import check_deadline
    check_deadline("spmd.dispatch")
    _faults.fault_point(_fltn.SPMD_DISPATCH)
    with _trace.span(SN.SPMD_DISPATCH, mode="agg") as sp:
        table, attempts = _run_impl(plan, executor, session)
        if sp is not None:
            sp.attrs["rows"] = int(table.num_rows)
            # The QUERY-LOCAL attempt count: the LAST_CAP_ATTEMPTS
            # module global is last-dispatch observability for
            # single-threaded tests/bench — a concurrent query may
            # overwrite it before this span closes.
            sp.attrs["cap_attempts"] = attempts
        return table


def _run_impl(plan: Aggregate, executor, session=None
              ) -> Tuple[Table, int]:
    global DISPATCH_COUNT, LAST_CAP_ATTEMPTS
    with _COUNT_LOCK:
        LAST_CAP_ATTEMPTS = 1
    caps: Dict[int, Tuple[int, int]] = {}
    # Prepared ONCE: leaf IO, join-side materialization, and sharding don't
    # depend on caps — only the jitted program (static shapes) does, so
    # escalation retries recompile but never redo IO.
    with _trace.span(SN.SPMD_PREPARE):
        prep = _prepare(plan.child, executor, caps, session)

        def probe(e: E.Expr) -> Column:
            t = {n: Column(dt, jnp.zeros(0, _DEVICE_DTYPE[dt]),
                           jnp.zeros(0, jnp.bool_) if nul else None, dic)
                 for n, (dt, dic, nul) in prep.final_meta.items()}
            return eval_expr(Table(t), e)

        agg_specs = tuple(_AggSpec.build(a, probe) for a in plan.aggs)
    group_cols = tuple(plan.group_cols)
    for g in group_cols:
        if g not in prep.final_meta:
            raise _Unsupported(f"unknown group column {g}")
    grouped = bool(group_cols)
    n_dev = prep.mesh.devices.size
    G2 = 0  # sized from G on first iteration
    cap_attempts = 0
    gmof_retried = False
    gof_retried = False
    G_floor = 0  # raised by the one-shot local-capacity retry
    if n_dev == 1:
        # One device: the local partials ARE the final groups (no merge
        # exchange follows), so size them at the hard bound, the rows.
        # The program then cannot overflow and the query compiles once,
        # where a 64k start overflows on TPC-H Q3 at SF1 (644,481 groups)
        # and compiles the program twice, minutes each on a v5e (PR 21).
        # _merge_grouped fetches only the valid prefix.
        G_floor = _out_rows(prep, caps)
    routed = _use_routed_merge(prep.mesh)
    while True:
        # MAX_LOCAL_GROUPS is the INITIAL local-partial capacity, not a
        # ceiling (VERDICT r5 #6: TPC-DS groups by customer/item keys blow
        # 65k immediately): on overflow the program reports the exact
        # worldwide need and one retry re-runs with that many slots
        # (bounded by per-device rows — distinct groups can't exceed them).
        G = min(_out_rows(prep, caps), MAX_LOCAL_GROUPS)
        G = min(max(G, G_floor), _out_rows(prep, caps))
        G2 = min(max(G2, G), n_dev * G)
        descr = _StageDescr(prep.stages, prep.joins, prep.col_meta,
                            agg_specs, group_cols, dict(caps),
                            prep.project_live)
        with _trace.span(SN.SPMD_LAUNCH):
            out = _spmd_program(prep.sharded, prep.valid, prep.bcast,
                                prep.xch, mesh=prep.mesh, descr=descr,
                                grouped=grouped, G=G, G2=G2, mode="agg",
                                routed_merge=routed)
        if _escalate_on_overflow(out, caps):
            cap_attempts += 1
            n_xch = sum(1 for j in prep.joins.values() if j[0] == "x")
            if cap_attempts > _MAX_CAP_RETRIES * max(n_xch, 1):
                raise _Unsupported(
                    "exchange join capacity escalation exhausted")
            with _COUNT_LOCK:
                LAST_CAP_ATTEMPTS = cap_attempts + 1
            # New caps → new partial-group distribution; the one-shot
            # owner-capacity retry becomes available again.
            gmof_retried = False
            continue
        if grouped:
            if bool(_fetch("spmd.overflow", out["overflow"])):
                if gof_retried:
                    raise _Unsupported("local group capacity overflow "
                                       "after exact-need retry")
                gof_retried = True
                need = int(_fetch("spmd.overflow", out["gneed"]))
                G_floor = min(_round_up_pow2(need),
                              _out_rows(prep, caps))
                gmof_retried = False  # new G → new owner distribution
                continue
            if routed and bool(_fetch("spmd.overflow", out["gmof"])):
                # One owner device holds more than G2 distinct groups
                # (hash skew). The program reports the exact capacity
                # needed, so ONE retry — with its own budget, not the
                # exchange-cap one — always suffices (rounded up to a
                # multiple of G to keep the jit cache coarse; hard bound:
                # total groups ≤ n_dev*G).
                if gmof_retried:
                    raise _Unsupported("merge capacity retry failed")
                gmof_retried = True
                need = int(_fetch("spmd.overflow", out["gmneed"]))
                G2 = min(max(G2 + 1, -(-need // G) * G), n_dev * G)
                continue
            with _trace.span(SN.SPMD_MERGE):
                table = _merge_grouped(out, agg_specs, list(group_cols),
                                       prep.final_meta, prefix=n_dev == 1)
        else:
            with _trace.span(SN.SPMD_MERGE):
                table = _merge_global(out, agg_specs, prep.final_meta)
        with _COUNT_LOCK:
            DISPATCH_COUNT += 1
        _record_join_actuals(session, prep, out)
        # Emit the query-local attempt count, not the module global: a
        # concurrent dispatch may have reset LAST_CAP_ATTEMPTS already.
        _emit_spmd_events(session,
                          "grouped-agg" if grouped else "global-agg",
                          prep, caps, cap_attempts + 1)
        return table, cap_attempts + 1


def _run_stream(root, executor, sort_orders=(), session=None) -> Table:
    """Dispatch wrapper for the row-returning path — see :func:`_run`."""
    from ..robustness import fault_names as _fltn
    from ..robustness import faults as _faults
    from ..serving.context import check_deadline
    check_deadline("spmd.dispatch")
    _faults.fault_point(_fltn.SPMD_DISPATCH)
    mode = "sort" if sort_orders else "stream"
    with _trace.span(SN.SPMD_DISPATCH, mode=mode) as sp:
        table, attempts = _run_stream_impl(root, executor, sort_orders,
                                           session)
        if sp is not None:
            sp.attrs["rows"] = int(table.num_rows)
            sp.attrs["cap_attempts"] = attempts  # query-local; see _run
        return table


def _run_stream_impl(root, executor, sort_orders=(), session=None
                     ) -> Tuple[Table, int]:
    """Row-returning SPMD execution of a {Filter, Project, Join}* chain:
    every device runs the stages on its shard, the host gathers each
    device's valid rows and concatenates (VERDICT r3 #3a). With
    ``sort_orders``, the program additionally range-partitions and sorts
    on device (sample sort), so the gathered rows arrive globally sorted
    and the host does NO sort work."""
    global DISPATCH_COUNT, SORT_DISPATCH_COUNT, LAST_CAP_ATTEMPTS
    with _COUNT_LOCK:
        LAST_CAP_ATTEMPTS = 1
    caps: Dict[int, Tuple[int, int]] = {}
    with _trace.span(SN.SPMD_PREPARE):
        prep = _prepare(root, executor, caps, session)  # once; see _run
    out_names = [n for n in root.schema.names if n in prep.final_meta]
    if not out_names:
        raise _Unsupported("no output columns")
    mode = "stream"
    if sort_orders:
        mode = "sort"
        for n, _asc in sort_orders:
            if n not in prep.final_meta:
                raise _Unsupported(f"sort key {n!r} not in stream output")
        # Initial per-(src, dst) send block: 2x the balanced share;
        # sorted/skewed inputs overflow once and retry with the exact
        # reported need (same mechanism as the exchange joins, keyed -1).
        caps[-1] = (_round_up_pow2(
            max(2 * prep.shard_rows // prep.n_dev, 128)), 0)
    out_pairs = tuple((n, prep.final_meta[n][2]) for n in out_names)
    n_xch = sum(1 for j in prep.joins.values() if j[0] == "x")
    for attempt in range(_MAX_CAP_RETRIES * (n_xch + 1) + 1):
        with _COUNT_LOCK:
            LAST_CAP_ATTEMPTS = attempt + 1
        descr = _StageDescr(prep.stages, prep.joins, prep.col_meta,
                            (), out_pairs, dict(caps), prep.project_live,
                            sort_orders=tuple(sort_orders))
        with _trace.span(SN.SPMD_LAUNCH):
            out = _spmd_program(prep.sharded, prep.valid, prep.bcast,
                                prep.xch, mesh=prep.mesh, descr=descr,
                                grouped=False, G=1, mode=mode)
        if _escalate_on_overflow(out, caps):
            continue
        with _trace.span(SN.SPMD_MERGE):
            mask = _fetch("spmd.gather", out["omask"])
            cols: Dict[str, Column] = {}
            for n in out_names:
                dt, dic, nul = prep.final_meta[n]
                data = _fetch("spmd.gather", out[f"o:{n}"])[mask]
                validity = None
                if f"ov:{n}" in out:
                    validity = jnp.asarray(
                        _fetch("spmd.gather", out[f"ov:{n}"])[mask])
                cols[n] = Column(dt, jnp.asarray(data), validity, dic)
        with _COUNT_LOCK:
            DISPATCH_COUNT += 1
            if mode == "sort":
                SORT_DISPATCH_COUNT += 1
        _record_join_actuals(session, prep, out)
        # Query-local attempt count (see _run): the module global is
        # last-dispatch observability only.
        _emit_spmd_events(session, mode, prep, caps, attempt + 1)
        return Table(cols), attempt + 1
    raise _Unsupported("exchange join capacity escalation exhausted")


def _round_up_pow2(n: int) -> int:
    """Retry capacities round up to a power of two: ≤2× memory waste and a
    coarse jit-cache key (many different exact needs share one program)."""
    return max(128, 1 << max(int(n) - 1, 1).bit_length())


def _escalate_on_overflow(out, caps: Dict[int, Tuple[int, int]]) -> bool:
    """True if any exchange join overflowed its capacity; caps are set in
    place from the EXACT needs the program reported, so one recompile
    suffices in the common case (VERDICT r3 #6 — a blind ×4 ladder would
    recompile up to 4 programs per query on a backend where each compile
    can kill the remote-compile service).

    The send-block need (``xneedc``) is measured before slot clamping and
    is always exact. The output-slot need (``xneedo``) is exact only when
    the send side fit — a clamped receive undercounts matches — so after a
    send overflow (``xneedc`` above cap) the retry doubles the reported output need as
    a safety margin; the attempt after that sees exact numbers. Worst case
    is therefore 2 retries (skewed send), 1 in the common case."""
    bumped = False
    for key in out:
        if not key.startswith("xof:"):
            continue
        i = int(key.split(":")[1])
        if bool(_fetch("spmd.overflow", out[key])):
            cap, k_out = caps[i]
            need_c = int(_fetch("spmd.overflow", out[f"xneedc:{i}"]))
            need_o = int(_fetch("spmd.overflow", out[f"xneedo:{i}"]))
            send_of = need_c > cap  # definitionally the send overflow
            new_cap = max(cap, _round_up_pow2(need_c))
            new_out = max(k_out, _round_up_pow2(
                need_o * 2 if send_of else need_o))
            caps[i] = (new_cap, new_out)
            bumped = True
    return bumped


class _StageDescr:
    """Static (hashable) description of the SPMD program. The hash is a
    *structural* signature so repeated executions of the same query shape
    hit the jit cache instead of recompiling; string dictionaries are part
    of the key because they become trace-time constants.

    ``group_cols`` doubles as the output-column list in stream mode (the
    program has no grouping there). ``caps`` maps exchange-join stage index
    → (send capacity per destination, output slots per device)."""

    def __init__(self, stages, joins, col_meta, agg_specs, group_cols,
                 caps, project_live, sort_orders=()):
        self.stages = stages
        self.joins = joins
        self.col_meta = col_meta
        self.agg_specs = agg_specs
        self.group_cols = group_cols
        self.caps = caps
        self.project_live = project_live
        self.sort_orders = tuple(sort_orders)
        parts: List = [group_cols, tuple(sorted(caps.items())),
                       self.sort_orders,
                       tuple(sorted((i, tuple(sorted(v)))
                             for i, v in project_live.items()))]
        for i, (kind, node) in enumerate(stages):
            if kind == "filter":
                parts.append(("F", repr(node.condition)))
            elif kind == "project":
                parts.append(("P", tuple(repr(e) for e in node.exprs)))
            else:
                jkind, pairs, side, jt = joins[i]
                parts.append(("J", jkind, jt, repr(node.condition),
                              tuple(node.schema.names), side.pack,
                              side.probe if jkind == "b" else None))
        for n, (dt, dic, nul) in sorted(col_meta.items()):
            parts.append((n, dt, _dict_fingerprint(dic), nul))
        for s in agg_specs:
            parts.append((s.name, s.kind, repr(s.child), s.out_dtype,
                          _dict_fingerprint(s.dictionary)))
        self._sig = tuple(parts)

    def __hash__(self):
        return hash(self._sig)

    def __eq__(self, other):
        return isinstance(other, _StageDescr) and self._sig == other._sig


def _stream_probe_key(table: Table, pairs, pack) -> Tuple[jax.Array, jax.Array]:
    """(probe key array, all-keys-valid mask) for a join stage. Single-key
    joins probe the raw column; multi-key joins build the bit-packed
    composite using the broadcast side's (rmin, shift, sentinel) spec —
    out-of-range stream values map to the sentinel, which never matches."""
    if pack is None:
        lc = table.column(pairs[0][0])
        valid = lc.validity if lc.validity is not None \
            else jnp.ones(lc.data.shape[0], jnp.bool_)
        return lc.data, valid
    comp = None
    valid = None
    for (lname, _), (rmin, shift, sentinel) in zip(pairs, pack):
        lc = table.column(lname)
        c = lc.data.astype(jnp.int64)
        code = jnp.where((c >= rmin) & (c <= rmin + sentinel - 1),
                         c - rmin, sentinel)
        comp = (code << shift) if comp is None else comp | (code << shift)
        v = lc.validity
        if v is not None:
            valid = v if valid is None else (valid & v)
    if valid is None:
        valid = jnp.ones(comp.shape[0], jnp.bool_)
    return comp, valid


def _direct_probe(lk, slot, kmin) -> Tuple[jax.Array, jax.Array]:
    """(found, broadcast row) of each stream key through the side's
    position table: one gather at ``lk - kmin``. A difference that wraps
    in the key's width can land inside the table only at an offset whose
    key would lie outside that width, so its slot holds -1."""
    off = lk - kmin[0]
    length = slot.shape[0]
    in_range = (off >= 0) & (off < length)
    pos = jnp.take(slot, jnp.clip(off, 0, length - 1).astype(jnp.int32),
                   mode="clip")
    return in_range & (pos >= 0), jnp.maximum(pos, 0)


def _use_routed_merge(mesh: Mesh) -> bool:
    """Backend cost decision for the grouped final merge: route partial
    groups to owner devices over the mesh collective (real multi-chip —
    the merge then scales with devices and the host only concatenates), or
    hand the partials straight to the host merge (single-host CPU mesh:
    the 'devices' share the silicon the host merge runs on, so the
    exchange is pure added work). HST_SPMD_ROUTED_MERGE=on|off overrides."""
    mode = os.environ.get("HST_SPMD_ROUTED_MERGE", "auto")
    if mode in ("on", "off"):
        return mode == "on"
    return mesh.devices.flat[0].platform != "cpu"


def _group_segments(mask, flags, datas, cap: int):
    """Shared grouping step for the local-partial AND owner-merge phases:
    sort rows by (masked-out last, [null-flag, value] per key column),
    detect group boundaries, and assign capacity-bounded segment ids.

    Returns (order, sorted mask, sorted flags, sorted datas, gids,
    n_groups): ``gids`` carries ``cap`` for masked-out rows (segment ops
    drop them); ``n_groups`` is the distinct count before clamping —
    overflow iff > cap.

    A ``None`` flag marks a column without nulls: it stays out of the sort
    (and comes back as None), since the TPU compile time of a sort grows
    steeply with its operand count."""
    sort_ops = [(~mask).astype(jnp.int32)]
    for f, d in zip(flags, datas):
        sort_ops.extend([d] if f is None else [f, d])
    order = kernels.lex_sort_indices(sort_ops)
    s_mask = jnp.take(mask, order)
    s_flags = [None if f is None else jnp.take(f, order) for f in flags]
    s_datas = [jnp.take(d, order) for d in datas]
    n = s_mask.shape[0]
    change = jnp.zeros(n, jnp.bool_)
    for arr in [f for f in s_flags if f is not None] + s_datas:
        change = change | jnp.concatenate(
            [jnp.zeros(1, jnp.bool_), arr[1:] != arr[:-1]])
    first = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), jnp.zeros(n - 1, jnp.bool_)])
    newg = s_mask & (change | first)
    gids_raw = jnp.cumsum(newg.astype(jnp.int32)) - 1
    gids = jnp.where(s_mask, gids_raw, cap)
    n_groups = jnp.max(jnp.where(s_mask, gids_raw + 1, 0))
    return order, s_mask, s_flags, s_datas, gids, n_groups


def _a2a_exchange(arrays: Dict[str, jax.Array], send_ok: jax.Array,
                  dst: jax.Array, n_dev: int, cap: int):
    """Route rows to their destination device with ONE lax.all_to_all.
    ``dst`` in [0, n_dev); rows with ``send_ok`` False are dropped. Returns
    (received arrays, received-valid mask, overflow flag, exact need) —
    overflow is raised (pmax) when any (device, destination) block exceeds
    ``cap``; ``need`` is the worldwide max block count, i.e. the exact
    capacity a retry must allocate (counts are measured BEFORE clamping,
    so the need is reliable even on overflow)."""
    rows = send_ok.shape[0]
    dst = jnp.where(send_ok, dst, n_dev)  # drop → virtual device n_dev
    perm = kernels.lex_sort_indices([dst])
    sorted_dst = jnp.take(dst, perm)
    starts = jnp.searchsorted(sorted_dst,
                              jnp.arange(n_dev + 1, dtype=sorted_dst.dtype))
    counts = starts[1:] - starts[:-1]
    overflow = jax.lax.pmax(jnp.any(counts > cap).astype(jnp.int32),
                            DATA_AXIS)
    need = jax.lax.pmax(jnp.max(counts).astype(jnp.int32), DATA_AXIS)
    pos = jnp.arange(rows, dtype=jnp.int32) - jnp.take(
        starts, jnp.minimum(sorted_dst, n_dev)).astype(jnp.int32)
    slot_ok = (pos < cap) & (sorted_dst < n_dev)
    send_idx = jnp.where(slot_ok, sorted_dst * cap + pos, n_dev * cap)

    def scatter(arr):
        taken = jnp.take(arr, perm, axis=0)
        buf = jnp.zeros((n_dev * cap + 1,) + arr.shape[1:], arr.dtype)
        return buf.at[send_idx].set(taken, mode="drop")[:-1]

    def a2a(x):
        return jax.lax.all_to_all(
            x.reshape((n_dev, cap) + x.shape[1:]), DATA_AXIS,
            split_axis=0, concat_axis=0).reshape((n_dev * cap,) + x.shape[1:])

    recv = {name: a2a(scatter(a)) for name, a in arrays.items()}
    recv_valid = a2a(jnp.zeros(n_dev * cap + 1, jnp.bool_)
                     .at[send_idx].set(slot_ok, mode="drop")[:-1])
    return recv, recv_valid, overflow, need


# (program, shape signature) of the most recent SPMD dispatch. Rebound
# (never mutated) per _spmd_program call; last_collectives() reads it
# lazily. The SIGNATURE is retained, not the arguments — live device
# arrays here would pin the last query's whole sharded input in device
# memory for as long as the process idles.
_LAST_PROGRAM: Optional[Tuple] = None


def last_collectives() -> Dict[str, int]:
    """HLO collective counts of the most recent SPMD program — computed
    lazily from the retained compiled executable (rendering HLO text is
    too expensive for the dispatch path) and cached per program."""
    if _LAST_PROGRAM is None:
        return {}
    prog, sig = _LAST_PROGRAM
    return prog.collectives_for(sig)


def _spmd_program(sharded, valid, bcast, xch, *, mesh: Mesh,
                  descr: _StageDescr, grouped: bool, G: int, mode: str,
                  G2: int = 1, routed_merge: bool = True):
    stages, joins, col_meta = descr.stages, descr.joins, descr.col_meta
    agg_specs, group_cols = descr.agg_specs, descr.group_cols
    n_dev = mesh.devices.size

    def per_device(sharded, valid, bcast, xch):
        cols = {}
        for key, arr in sharded.items():
            tag, name = key.split(":", 1)
            if tag != "d":
                continue
            dt, dic, _ = col_meta[name]
            cols[name] = Column(dt, arr, sharded.get(f"v:{name}"), dic)
        table = Table(cols)
        mask = valid
        overflow_flags = {}

        for i, (kind, node) in enumerate(stages):
            with jax.named_scope(f"stage{i}_{kind}"):
                if kind == "filter":
                    mask = mask & eval_predicate_mask(table, node.condition)
                elif kind == "project":
                    live = descr.project_live.get(i, frozenset())
                    table = Table({e.name: eval_expr(table, e)
                                   for e in node.exprs if e.name in live})
                elif joins[i][0] == "b":  # broadcast join probe
                    _, pairs, side, jt = joins[i]
                    lk, keys_valid = _stream_probe_key(table, pairs, side.pack)
                    direct = side.probe == "direct"
                    empty = not direct and bcast[f"k:{i}"].shape[0] == 0
                    if direct:
                        found, idx_c = _direct_probe(
                            lk, bcast[f"s:{i}"], bcast[f"m:{i}"])
                    elif empty:
                        found = jnp.zeros(lk.shape[0], jnp.bool_)
                        idx_c = jnp.zeros(lk.shape[0], jnp.int32)
                    else:
                        rkeys = bcast[f"k:{i}"]
                        idx = jnp.searchsorted(rkeys, lk)
                        idx_c = jnp.minimum(idx, rkeys.shape[0] - 1)
                        found = jnp.take(rkeys, idx_c) == lk
                    found = found & keys_valid
                    if jt == "semi":
                        mask = mask & found
                        continue
                    if jt == "anti":
                        # Null / unmatched keys match nothing → kept (the
                        # NOT IN non-null convention the executor documents).
                        mask = mask & ~found
                        continue
                    if jt == "inner":
                        mask = mask & found
                        # Observed join output rows (m:1 probe: one emit per
                        # surviving stream row) — psum'd so the host can
                        # write the actual back to the session's q-error
                        # store (optimizer/join_order pairing).
                        overflow_flags[f"jrows:{i}"] = jax.lax.psum(
                            jnp.sum(mask.astype(jnp.int32)), DATA_AXIS)
                    # left outer: mask unchanged — unmatched stream rows stay,
                    # with the right columns invalid below.
                    rnames = {r for _, r in pairs}
                    new_cols = dict(table.columns)
                    for n in side.table.names:
                        if n in rnames:
                            continue
                        rc = side.table.column(n)
                        if empty:
                            data = jnp.zeros(lk.shape[0],
                                             _DEVICE_DTYPE[rc.dtype])
                            vv = None
                        else:
                            data = jnp.take(bcast[f"b:{i}:{n}"], idx_c, axis=0)
                            vkey = f"bv:{i}:{n}"
                            vv = (jnp.take(bcast[vkey], idx_c)
                                  if vkey in bcast else None)
                        if jt == "left":
                            vv = found if vv is None else (vv & found)
                        new_cols[n] = Column(rc.dtype, data, vv, rc.dictionary)
                    for lname, rname in pairs:
                        if rname in node.schema.names \
                                and rname not in new_cols:
                            lc = table.column(lname)
                            # Matched rows: right key == left key by
                            # definition; left-outer padding rows carry a
                            # null right key.
                            vv = lc.validity
                            if jt == "left":
                                vv = found if vv is None else (vv & found)
                            new_cols[rname] = Column(lc.dtype, lc.data, vv,
                                                     lc.dictionary)
                    table = Table(new_cols)
                else:  # exchange (m:n shuffle) join
                    _, pairs, side, jt = joins[i]
                    cap, k_out = descr.caps[i]
                    lk, keys_valid = _stream_probe_key(table, pairs, side.pack)
                    preserve_left = jt in ("left", "full")
                    preserve_right = jt in ("right", "full")
                    # Preserved-left rows route even with a null key (they
                    # must surface as unmatched); a "kv" flag rides along so
                    # the merge still refuses to match them. Otherwise
                    # null-key rows are dropped at the send.
                    l_ok = mask if preserve_left else (mask & keys_valid)
                    # Routing hashes the key in the SAME code space on both
                    # sides, so equal keys land on one device. String keys are
                    # already translated into one dictionary — their codes
                    # hash as plain int32 (no dictionary needed for routing;
                    # equal codes ⇔ equal strings); multi-key composites are
                    # packed int64 on both sides.
                    dtype = INT32 if side.key_dtype == STRING \
                        else side.key_dtype
                    dst_l = (kernels.hash32_values(lk, dtype)
                             % np.uint32(n_dev)).astype(jnp.int32)
                    l_arrays = {"k": lk}
                    if preserve_left:
                        l_arrays["kv"] = keys_valid
                    for n in table.names:
                        c = table.column(n)
                        l_arrays[f"d:{n}"] = c.data
                        if c.validity is not None:
                            l_arrays[f"v:{n}"] = c.validity
                    recv_l, lvalid, of_l, need_l = _a2a_exchange(
                        l_arrays, l_ok, dst_l, n_dev, cap)

                    rk = xch[f"x:{i}:k"]
                    r_ok = xch[f"x:{i}:__valid"]
                    dst_r = (kernels.hash32_values(rk, dtype)
                             % np.uint32(n_dev)).astype(jnp.int32)
                    r_arrays = {n[len(f"x:{i}:"):]: a for n, a in xch.items()
                                if n.startswith(f"x:{i}:") and
                                not n.endswith("__valid")}
                    recv_r, rvalid, of_r, need_r = _a2a_exchange(
                        r_arrays, r_ok, dst_r, n_dev, cap)
                    overflow_flags[f"xof:{i}"] = jnp.maximum(of_l, of_r)
                    # Exact retry sizing: worst (src, dst) block over both
                    # sides. Send overflow is recoverable host-side as
                    # need > cap, so no separate flag rides along.
                    overflow_flags[f"xneedc:{i}"] = jnp.maximum(need_l, need_r)

                    # Local merge join: right sorted (valid first, by key),
                    # invalid tail pinned to the key dtype's max so the whole
                    # array stays ascending for searchsorted; hi is clamped to
                    # the valid prefix length. Because equal keys all meet on
                    # one device, LOCAL match status is GLOBAL match status —
                    # which is what lets outer joins emit their unmatched
                    # rows here without any further coordination.
                    rkr = recv_r["k"]
                    # Key-valid ∧ receive-valid: null-key right rows (carried
                    # only under right/full, flagged "kv") must never match
                    # but still appendix as unmatched.
                    rkeyok = rvalid
                    if "kv" in recv_r:
                        rkeyok = rvalid & recv_r["kv"]
                    sort_r = kernels.lex_sort_indices(
                        [(~rkeyok).astype(jnp.int32), rkr])
                    rk_sorted = jnp.take(rkr, sort_r)
                    rvalid_sorted = jnp.take(rvalid, sort_r)
                    rkeyok_sorted = jnp.take(rkeyok, sort_r)
                    n_valid_r = jnp.sum(rkeyok.astype(jnp.int32))
                    rk_probe = jnp.where(rkeyok_sorted, rk_sorted,
                                         _max_sentinel(rk_sorted.dtype))
                    lkr = recv_l["k"]
                    lkvalid = lvalid
                    if preserve_left:
                        lkvalid = lvalid & recv_l["kv"]
                    lo = jnp.searchsorted(rk_probe, lkr, side="left")
                    hi = jnp.minimum(
                        jnp.searchsorted(rk_probe, lkr, side="right"),
                        n_valid_r)
                    matched_counts = jnp.where(
                        lkvalid, jnp.maximum(hi - lo, 0), 0).astype(jnp.int32)
                    if preserve_left:
                        # Every received stream row emits at least once.
                        emit_counts = jnp.where(
                            lvalid, jnp.maximum(matched_counts, 1), 0)
                    else:
                        emit_counts = matched_counts
                    total_l = jnp.sum(emit_counts)
                    n_l = lkr.shape[0]
                    li = jnp.repeat(jnp.arange(n_l, dtype=jnp.int32),
                                    emit_counts, total_repeat_length=k_out)
                    starts_ = jnp.cumsum(emit_counts) - emit_counts
                    base = jnp.repeat(starts_.astype(jnp.int32), emit_counts,
                                      total_repeat_length=k_out)
                    within = jnp.arange(k_out, dtype=jnp.int32) - base
                    is_match = within < jnp.take(matched_counts, li)
                    ri = jnp.repeat(lo.astype(jnp.int32), emit_counts,
                                    total_repeat_length=k_out) + \
                        jnp.where(is_match, within, 0)
                    ri = jnp.clip(ri, 0, max(rkr.shape[0] - 1, 0))

                    if preserve_right:
                        # Right rows whose key no received left row carries
                        # emit once, appended after the matched block. The
                        # left keys need their own sort for the probe.
                        sort_l = kernels.lex_sort_indices(
                            [(~lkvalid).astype(jnp.int32), lkr])
                        lk_sorted = jnp.take(lkr, sort_l)
                        lkv_sorted = jnp.take(lkvalid, sort_l)
                        n_valid_l = jnp.sum(lkvalid.astype(jnp.int32))
                        lk_probe = jnp.where(lkv_sorted, lk_sorted,
                                             _max_sentinel(lk_sorted.dtype))
                        lo_r = jnp.searchsorted(lk_probe, rk_sorted,
                                                side="left")
                        hi_r = jnp.minimum(
                            jnp.searchsorted(lk_probe, rk_sorted,
                                             side="right"),
                            n_valid_l)
                        r_unmatched = rvalid_sorted & \
                            (~rkeyok_sorted | ((hi_r - lo_r) <= 0))
                        appendix = jnp.sum(r_unmatched.astype(jnp.int32))
                        appendix_pos = total_l + jnp.cumsum(
                            r_unmatched.astype(jnp.int32)) - 1
                        # mode="drop" discards slots at/above k_out.
                        appendix_slot = jnp.where(r_unmatched, appendix_pos,
                                                  k_out).astype(jnp.int32)
                        total_eff = total_l + appendix
                    else:
                        appendix_slot = None
                        total_eff = total_l
                    overflow_flags[f"xof:{i}"] = jnp.maximum(
                        overflow_flags[f"xof:{i}"],
                        jax.lax.pmax((total_eff > k_out).astype(jnp.int32),
                                     DATA_AXIS))
                    # Exact per-device output need (counts are computed before
                    # any slot clamping, so this is exact whenever the send
                    # side fit — xneedc above cap marks the exception).
                    overflow_flags[f"xneedo:{i}"] = jax.lax.pmax(
                        total_eff.astype(jnp.int32), DATA_AXIS)
                    out_mask = jnp.arange(k_out, dtype=jnp.int32) < total_eff

                    live = jnp.arange(k_out, dtype=jnp.int32) < total_l
                    new_cols = {}
                    for n in table.names:
                        # Stream meta snapshot from prep time: projects below
                        # this join may have created/redefined columns the
                        # leaf col_meta doesn't describe.
                        dt, dic, _ = side.stream_meta[n]
                        data = jnp.take(recv_l[f"d:{n}"], li, axis=0)
                        vv = (jnp.take(recv_l[f"v:{n}"], li)
                              if f"v:{n}" in recv_l else None)
                        if preserve_right:
                            # Appendix rows have no left side: null-pad.
                            vv = live if vv is None else (vv & live)
                        new_cols[n] = Column(dt, data, vv, dic)
                    for n, (dt, dic, nul) in side.table_meta.items():
                        col_sorted = jnp.take(recv_r[f"d:{n}"], sort_r, axis=0)
                        data = jnp.take(col_sorted, ri, axis=0)
                        vv = (jnp.take(jnp.take(recv_r[f"v:{n}"], sort_r), ri)
                              if f"v:{n}" in recv_r else None)
                        if preserve_left:
                            # Unmatched stream rows: right side is null.
                            vv = is_match if vv is None else (vv & is_match)
                        if preserve_right:
                            base_v = vv if vv is not None else \
                                jnp.ones(k_out, jnp.bool_)
                            scat_v = (jnp.take(recv_r[f"v:{n}"], sort_r)
                                      if f"v:{n}" in recv_r
                                      else jnp.ones(rkr.shape[0], jnp.bool_))
                            data = data.at[appendix_slot].set(
                                col_sorted, mode="drop")
                            vv = base_v.at[appendix_slot].set(
                                scat_v, mode="drop")
                        new_cols[n] = Column(dt, data, vv, dic)
                    if side.pack is None and not preserve_right:
                        # Single-key, no appendix: the right key column is
                        # rebuilt for free from the stream key (equal on
                        # matches, null on left-outer padding) instead of
                        # riding the exchange as duplicate payload.
                        lname, rname = pairs[0]
                        if rname in node.schema.names \
                                and rname not in new_cols:
                            lcm = side.stream_meta[lname]
                            data = jnp.take(recv_l[f"d:{lname}"], li, axis=0)
                            vv = (jnp.take(recv_l[f"v:{lname}"], li)
                                  if f"v:{lname}" in recv_l else None)
                            if preserve_left:
                                vv = is_match if vv is None else \
                                    (vv & is_match)
                            new_cols[rname] = Column(lcm[0], data, vv, lcm[1])
                    table = Table(new_cols)
                    mask = out_mask
                    if jt == "inner":
                        # Emitted match pairs across the mesh (inner: every
                        # emit is a match; preserved-outer shapes are not
                        # recorded, matching the executor's actuals policy).
                        overflow_flags[f"jrows:{i}"] = jax.lax.psum(
                            total_eff.astype(jnp.int32), DATA_AXIS)

        if mode == "sort":
            with jax.named_scope("sample_sort"):
                # Distributed ORDER BY: range-partitioned sample sort (the
                # TPU-native analogue of Spark's range-partitioned global
                # sort consumed via exchange planning). Each device samples
                # its primary sort key, splitters come back over one
                # all_gather, rows route with one all_to_all, and each
                # device's local lex sort finishes the job — the host then
                # concatenates ALREADY-SORTED device ranges in rank order.
                k0, asc0 = descr.sort_orders[0]
                c0 = table.column(k0)
                view = kernels._sort_key_view(c0.data, asc0)
                if c0.validity is not None:
                    # Null placement (nulls first when ascending, last when
                    # descending) holds in view space by routing nulls to the
                    # extreme sentinel; the local sort places them exactly.
                    sentinel = _min_sentinel(view.dtype) if asc0 \
                        else _max_sentinel(view.dtype)
                    view = jnp.where(c0.validity, view, sentinel)

                order0 = kernels.lex_sort_indices(
                    [(~mask).astype(jnp.int32), view])
                sorted_view = jnp.take(view, order0)
                v_count = jnp.sum(mask.astype(jnp.int32))
                k = _SORT_SAMPLES
                pos = jnp.minimum((jnp.arange(k, dtype=jnp.int32) * v_count)
                                  // k, jnp.maximum(v_count - 1, 0))
                samples = jnp.where(
                    v_count > 0, jnp.take(sorted_view, pos),
                    jnp.full(k, _max_sentinel(view.dtype), view.dtype))
                all_samples = jax.lax.all_gather(
                    samples, DATA_AXIS).reshape(-1)
                all_sorted = jnp.sort(all_samples)
                total = n_dev * k
                spl_pos = (jnp.arange(1, n_dev, dtype=jnp.int32) * total) \
                    // n_dev
                splitters = jnp.take(all_sorted, spl_pos)
                dst = jnp.searchsorted(splitters, view,
                                       side="right").astype(jnp.int32)

                arrays = {}
                for n, nul in group_cols:
                    c = table.column(n)
                    arrays[f"d:{n}"] = c.data
                    if nul:
                        arrays[f"v:{n}"] = c.validity \
                            if c.validity is not None \
                            else jnp.ones(c.data.shape[0], jnp.bool_)
                cap = descr.caps[-1][0]
                recv, rvalid, of, need = _a2a_exchange(
                    arrays, mask, dst, n_dev, cap)
                out = dict(overflow_flags)
                out["xof:-1"] = of
                out["xneedc:-1"] = need
                out["xneedo:-1"] = need

                keys = [(~rvalid).astype(jnp.int32)]
                ascs = [True]
                for n, asc in descr.sort_orders:
                    vkey = f"v:{n}"
                    data = recv[f"d:{n}"]
                    if vkey in recv:
                        keys.append(recv[vkey].astype(jnp.int32))
                        ascs.append(asc)
                        data = jnp.where(recv[vkey], data,
                                         jnp.zeros((), data.dtype))
                    keys.append(data)
                    ascs.append(asc)
                final = kernels.lex_sort_indices(keys, ascs)
                out["omask"] = jnp.take(rvalid, final)
                for n, nul in group_cols:
                    out[f"o:{n}"] = jnp.take(recv[f"d:{n}"], final, axis=0)
                    if nul:
                        out[f"ov:{n}"] = jnp.take(recv[f"v:{n}"], final)
                return out

        if mode == "stream":
            # group_cols doubles as ((name, nullable), ...) in stream mode.
            out = dict(overflow_flags)
            out["omask"] = mask
            for n, nul in group_cols:
                c = table.column(n)
                out[f"o:{n}"] = c.data
                if nul:
                    out[f"ov:{n}"] = c.validity if c.validity is not None \
                        else jnp.ones(c.data.shape[0], jnp.bool_)
            return out

        if not grouped:
            fold = {
                "sum": lambda v: jax.lax.psum(jnp.sum(v), DATA_AXIS),
                "min": lambda v: jax.lax.pmin(jnp.min(v), DATA_AXIS),
                "max": lambda v: jax.lax.pmax(jnp.max(v), DATA_AXIS),
            }
            out = dict(overflow_flags)
            for spec in agg_specs:
                for k, v in spec.partials(table, mask, fold).items():
                    out[f"{spec.name}:{k}"] = v
            return out

        # ---- grouped: capacity-bounded local partials ----
        # Null-aware (flag, data) encoding per key: null(0) sorts first.
        key_flags, key_datas = [], []
        for g in group_cols:
            c = table.column(g)
            if c.validity is not None:
                flag = c.validity.astype(jnp.int32)
                data = jnp.where(c.validity, c.data,
                                 jnp.zeros((), c.data.dtype))
            else:
                flag = None  # no nulls: all-ones flag, kept out of the sort
                data = c.data
            key_flags.append(flag)
            key_datas.append(data)
        with jax.named_scope("group_sort"):
            order, s_mask, s_flags, s_datas, gids, local_groups = \
                _group_segments(mask, key_flags, key_datas, G)
        n_rows = s_mask.shape[0]
        overflow = jax.lax.pmax((local_groups > G).astype(jnp.int32),
                                DATA_AXIS)
        # Exact worldwide need: a local-capacity overflow retries ONCE
        # with this (distinct groups ≤ rows, so the retry always fits).
        gneed = jax.lax.pmax(local_groups, DATA_AXIS)

        with jax.named_scope("partials"):
            s_table = table.take(order)
            fold = {
                "sum": lambda v: kernels.segment_sum(v, gids, G),
                "min": lambda v: kernels.segment_min(v, gids, G),
                "max": lambda v: kernels.segment_max(v, gids, G),
            }
            out = {"overflow": overflow, "gneed": gneed}
            out.update(overflow_flags)
            for spec in agg_specs:
                for k, v in spec.partials(s_table, s_mask, fold).items():
                    out[f"{spec.name}:{k}"] = v
            firsts = jnp.minimum(kernels.segment_first_index(gids, G),
                                 n_rows - 1)
            for g, flag, data in zip(group_cols, s_flags, s_datas):
                out[f"g:{g}"] = jnp.take(data, firsts)
                out[f"gf:{g}"] = jnp.ones(G, jnp.int32) if flag is None \
                    else jnp.take(flag, firsts)
            out["gvalid"] = (jnp.arange(G, dtype=jnp.int32)
                             < jnp.minimum(local_groups, G))

        # ---- distributed final merge (the "final shuffle" on device) ----
        # Each partial group is hash-routed to its owner device with one
        # all_to_all and combined there, so the host receives DISJOINT
        # final groups and merely concatenates (its reduceat degenerates
        # to identity). cap=G can't overflow: a source device holds at
        # most G valid partial groups total. Owner-side capacity G2
        # escalates in _run (bounded by n_dev*G, the hard total).
        # ``routed_merge`` is a backend cost decision made by the caller:
        # on a VIRTUAL (single-host CPU) mesh the exchange adds work on
        # the same silicon the host merge would use, so the partials go
        # to the host merge instead; on real multi-chip the collective
        # rides ICI and the host stops being the merge bottleneck.
        if n_dev > 1 and routed_merge:
            with jax.named_scope("merge"):
                send = {k: v for k, v in out.items()
                        if k not in ("overflow", "gvalid", "gneed")
                        and not k.startswith(("xof:", "xneedc:",
                                              "xneedo:", "jrows:"))}
                gv = out["gvalid"]
                h = None
                for g in group_cols:
                    dt = table.column(g).dtype
                    ch = kernels.hash32_values(
                        out[f"g:{g}"], INT32 if dt == STRING else dt)
                    ch = kernels.hash_combine(
                        ch, out[f"gf:{g}"].astype(jnp.uint32))
                    h = ch if h is None else kernels.hash_combine(h, ch)
                dst = (h % np.uint32(n_dev)).astype(jnp.int32)
                recv, rvalid, _, _ = _a2a_exchange(send, gv, dst, n_dev, cap=G)
                order2, m2, sflags2, sdatas2, gids2, owned = _group_segments(
                    rvalid, [None if f is None else recv[f"gf:{g}"]
                             for g, f in zip(group_cols, key_flags)],
                    [recv[f"g:{g}"] for g in group_cols], G2)
                nr = m2.shape[0]
                out["gmof"] = jax.lax.pmax((owned > G2).astype(jnp.int32),
                                           DATA_AXIS)
                # Exact capacity an owner needs — _run retries ONCE with this
                # (rounded up) instead of stepping blindly.
                out["gmneed"] = jax.lax.pmax(owned, DATA_AXIS)
                for spec in agg_specs:
                    for k in spec.partial_keys():
                        v = jnp.take(recv[f"{spec.name}:{k}"], order2, axis=0)
                        if k == "min":
                            v = jnp.where(m2, v, _max_sentinel(v.dtype))
                            merged = kernels.segment_min(v, gids2, G2)
                        elif k == "max":
                            v = jnp.where(m2, v, _min_sentinel(v.dtype))
                            merged = kernels.segment_max(v, gids2, G2)
                        else:  # sum / count merge by summation
                            v = jnp.where(m2, v, jnp.zeros((), v.dtype))
                            merged = kernels.segment_sum(v, gids2, G2)
                        out[f"{spec.name}:{k}"] = merged
                firsts2 = jnp.minimum(kernels.segment_first_index(gids2, G2),
                                      nr - 1)
                for g, f2, d2 in zip(group_cols, sflags2, sdatas2):
                    out[f"g:{g}"] = jnp.take(d2, firsts2)
                    out[f"gf:{g}"] = jnp.ones(G2, jnp.int32) if f2 is None \
                        else jnp.take(f2, firsts2)
                out["gvalid"] = (jnp.arange(G2, dtype=jnp.int32)
                                 < jnp.minimum(owned, G2))
        else:
            out["gmof"] = jnp.zeros((), jnp.int32)
        return out

    xof_keys = [f"{tag}:{i}" for i, j in descr.joins.items() if j[0] == "x"
                for tag in ("xof", "xneedc", "xneedo")]
    # Replicated (psum'd) per-inner-join output counts — the SPMD-path
    # join actuals the host records after a successful dispatch.
    xof_keys += [f"jrows:{i}" for i, j in descr.joins.items()
                 if j[3] == "inner"]
    if mode == "sort":
        xof_keys += ["xof:-1", "xneedc:-1", "xneedo:-1"]
    if mode in ("stream", "sort"):
        out_specs: Dict[str, P] = {"omask": P(DATA_AXIS)}
        for n, nul in group_cols:
            out_specs[f"o:{n}"] = P(DATA_AXIS)
            if nul:
                out_specs[f"ov:{n}"] = P(DATA_AXIS)
    elif grouped:
        out_specs = {"overflow": P(), "gmof": P(), "gneed": P()}
        if mesh.devices.size > 1 and routed_merge:
            out_specs["gmneed"] = P()
        for spec in agg_specs:
            for k in spec.partial_keys():
                out_specs[f"{spec.name}:{k}"] = P(DATA_AXIS)
        for g in group_cols:
            out_specs[f"g:{g}"] = P(DATA_AXIS)
            out_specs[f"gf:{g}"] = P(DATA_AXIS)
        out_specs["gvalid"] = P(DATA_AXIS)
    else:
        out_specs = {f"{spec.name}:{k}": P()
                     for spec in agg_specs for k in spec.partial_keys()}
    for k in xof_keys:
        out_specs[k] = P()

    # A stable program name (the HLO module ``jit_spmd_<mode>``) and
    # named scopes for its phases, so a profile reads the same from
    # compile to compile; XLA's instruction names do not.
    program_name = f"spmd_{mode}"

    def global_view(sharded, valid, bcast, xch):
        with jax.named_scope(program_name):
            return device_view(
                per_device, mesh,
                in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(), P(DATA_AXIS)),
                out_specs=out_specs)(sharded, valid, bcast, xch)

    global_view.__name__ = global_view.__qualname__ = program_name

    # One bank entry per (stage fingerprint, mesh signature): the stage
    # fingerprint is the structural _StageDescr signature plus every
    # capacity/mode static — exactly what used to be the jit static-arg
    # key — so retries with escalated caps compile their own program while
    # repeated executions of the same query shape hit the bank (and two
    # sessions share it: the r11 cross-session contract now covers the
    # distributed tier).
    args = (sharded, valid, bcast, xch)
    prog = bank_program("exec", mesh,
                        (descr, grouped, G, G2, mode, routed_merge),
                        args, lambda: global_view)
    global _LAST_PROGRAM
    _LAST_PROGRAM = (prog, prog.signature(args))
    return prog(*args)


# ---------------------------------------------------------------------------
# Host-side merges.
# ---------------------------------------------------------------------------

def _fetch(site: str, x) -> np.ndarray:
    """One blocking device->host copy: a ``device.sync`` span at ``site``
    when the query is traced."""
    with _trace.span(SN.DEVICE_SYNC, node=site):
        return np.asarray(jax.device_get(x))


def _nullable_inputs(spec: _AggSpec, col_meta) -> bool:
    if spec.child is None:
        return False
    return any(col_meta.get(r, (None, None, False))[2]
               for r in spec.child.references)


def _merge_global(out, agg_specs, final_meta) -> Table:
    cols = {}
    for spec in agg_specs:
        merged = {k: np.atleast_1d(_fetch("spmd.merge",
                                          out[f"{spec.name}:{k}"]))
                  for k in spec.partial_keys()}
        cols[spec.name] = spec.finalize(
            merged, nullable_inputs=_nullable_inputs(spec, final_meta))
    return Table(cols)


def _merge_grouped(out, agg_specs, group_cols: List[str], col_meta,
                   prefix: bool = False) -> Table:
    if prefix:
        # One device: the valid groups are a prefix of row-sized slots;
        # fetch its length class, not the whole capacity.
        from .shapes import padded_length
        names = (["gvalid"] + [f"{p}:{g}" for p in ("g", "gf")
                               for g in group_cols]
                 + [f"{s.name}:{k}" for s in agg_specs
                    for k in s.partial_keys()])
        cap = int(out["gvalid"].shape[0])
        n = int(_fetch("spmd.merge", out["gneed"]))
        stop = min(max(padded_length(n), 1), cap)
        out = dict(out)
        out.update(zip(names, kernels.slice_arrays(
            [out[k] for k in names], 0, stop)))
    gvalid = _fetch("spmd.merge", out["gvalid"])
    sel = np.nonzero(gvalid)[0]
    keys = [_fetch("spmd.merge", out[f"g:{g}"])[sel] for g in group_cols]
    flags = [_fetch("spmd.merge", out[f"gf:{g}"])[sel] for g in group_cols]
    partials = {f"{s.name}:{k}": _fetch("spmd.merge",
                                        out[f"{s.name}:{k}"])[sel]
                for s in agg_specs for k in s.partial_keys()}

    # Merge-sort all per-device partial groups by (null-first, value) —
    # the same order the per-device sort used, and the output row order
    # (the single-device path also emits groups key-sorted).
    sort_cols: List[np.ndarray] = []
    for f, k in zip(flags, keys):
        # Flag before key: np.lexsort makes the *last* key primary, and
        # sort_cols is reversed below, so per group column the null-flag
        # must precede the value to be the more significant key — matching
        # the per-device (flag, data) sort order (null-first, since null
        # rows carry flag 0 and value 0, and negative values sort after
        # the null group only when the flag dominates).
        sort_cols.append(f)
        sort_cols.append(k)
    order = np.lexsort(tuple(reversed(sort_cols))) if sort_cols else \
        np.arange(len(sel))
    keys = [k[order] for k in keys]
    flags = [f[order] for f in flags]
    partials = {k: v[order] for k, v in partials.items()}

    n = len(order)
    if n == 0:
        boundaries = np.zeros(0, np.intp)
    else:
        change = np.zeros(n, bool)
        change[0] = True
        for arr in keys + flags:
            change[1:] |= arr[1:] != arr[:-1]
        boundaries = np.nonzero(change)[0]

    def reduceat(op, arr):
        return op.reduceat(arr, boundaries) if n else arr[:0]

    cols: Dict[str, Column] = {}
    for g, k, f in zip(group_cols, keys, flags):
        dt, dic, has_nulls = col_meta[g]
        validity = jnp.asarray(f[boundaries].astype(bool)) if has_nulls \
            else None
        cols[g] = Column(dt, jnp.asarray(k[boundaries]), validity, dic)
    for spec in agg_specs:
        merged = {}
        for k in spec.partial_keys():
            arr = partials[f"{spec.name}:{k}"]
            op = {"count": np.add, "sum": np.add,
                  "min": np.minimum, "max": np.maximum}[k]
            merged[k] = reduceat(op, arr)
        cols[spec.name] = spec.finalize(
            merged, nullable_inputs=_nullable_inputs(spec, col_meta))
    ordered = {g: cols[g] for g in group_cols}
    for spec in agg_specs:
        ordered[spec.name] = cols[spec.name]
    return Table(ordered)
