"""Explicit per-query execution context.

Before the serving tier, everything one query needed at runtime was
implicit per-``Session`` state: the result-cache handle was re-probed
from the session, advisor capture re-read the conf, the parallel-io
layer attributed reads to a session-wide pile, and the executor wrote
join cardinalities straight onto session attributes. That works for one
thread per session; a process-wide frontend multiplexing many sessions
over shared worker threads needs the per-query state to be an explicit
object it can build, hand to a worker, and inspect afterwards.

:class:`QueryContext` is that object. ``Session.execute`` creates one
per call (or accepts one from the serving frontend), activates it on a
contextvar for the duration of the execution, and every layer below —
the executor, the result cache, the parallel-io pool, the program bank
— reads the ACTIVE context instead of reaching for session attributes:

- ``result_cache``: resolved ONCE at context creation — the frontend's
  cross-session shared cache when the query came through the serving
  tier, else the session's own. Mid-query conf flips cannot swap the
  cache out from under an execution.
- ``capture``: the advisor-capture decision, pinned at creation for the
  same reason.
- ``io``: per-query read counters (tasks, bytes, seconds, waits) that
  ``parallel/io.py`` credits to the active context — so a multi-tenant
  frontend can attribute I/O to the query that caused it, not just to
  the process-wide pile.
- ``record_join_actual``: the executor's observed-join-cardinality
  write, routed through the context to the owning session's bounded
  store (locked — worker threads share sessions).

The contextvar (not a thread-local) matters: the prefetch producer and
the serving workers enter copied contexts (``contextvars.copy_context``),
so attribution follows the QUERY across threads, exactly like the io
session scope it generalizes.

No jax imports here — sessions (and config.py) must stay importable
without touching the execution stack.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from typing import Optional

# Process-wide monotonically increasing query ids (itertools.count is
# atomic under the GIL; the lock guards readers that want a stable
# snapshot semantics anyway).
_QUERY_IDS = itertools.count(1)

_CONTEXT: contextvars.ContextVar = contextvars.ContextVar(
    "hst_query_context", default=None)

_IO_COUNTER_KEYS = ("read_tasks", "read_bytes", "read_seconds",
                    "wait_seconds", "prefetch_items",
                    "pool_hits", "pool_misses", "pool_bytes_saved")


class QueryContext:
    """Everything one query execution needs, made explicit."""

    def __init__(self, session, result_cache=None, capture: Optional[bool]
                 = None, client: str = "", query_id: Optional[int] = None,
                 deadline_s: Optional[float] = None):
        self.session = session
        self.query_id = query_id if query_id is not None \
            else next(_QUERY_IDS)
        self.client = client
        self.created_s = time.perf_counter()
        # Resolved handles (pinned for the query's lifetime).
        self.result_cache = result_cache
        self.capture = bool(capture) if capture is not None else False
        # Cooperative deadline (robustness layer): an ABSOLUTE
        # perf_counter stamp, or None. Checked at the executor's
        # per-node stage boundary, the io wait loops, and SPMD dispatch
        # via :func:`check_deadline`; expiry raises the typed
        # QueryDeadlineError and emits ONE QueryCancelledEvent.
        self.deadline_s = deadline_s
        self._cancel_emitted = False
        # Unified tracing (telemetry/trace.py): ``trace`` is the Trace
        # this query's spans landed in (set by query_trace once tracing
        # is on); ``trace_parent`` is an optional (Trace, Span) pair a
        # literal-sweep batch hands in so member queries nest under ONE
        # shared sweep span instead of opening their own roots.
        self.trace = None
        self.trace_parent = None
        # ``trace_force`` (explain_analyze): open and RETAIN this
        # query's trace regardless of telemetry.trace.{enabled,
        # sampleRate}. ``degraded``: a robustness degradation ladder
        # fired during this query (faults.note sets it; the SLO
        # monitor's degrade-rate objective reads it).
        self.trace_force = False
        self.degraded = False
        # A SWEEP-member attempt whose failure the frontend's member
        # ladder will rescue with a standalone rerun: its error must
        # not land in the SLO window (the rerun records the query's
        # REAL outcome — counting both would show errors for queries
        # every client saw succeed).
        self.slo_suppress_error = False
        # What Session.execute turns the answer into before the query
        # ends (DataFrame.to_arrow: the host copy and the Arrow build),
        # timed inside the query's trace; None returns the device Table.
        self.finish = None
        # Per-query io counters; the lock is for cross-thread writers
        # (prefetch producers run in a copied context on another thread).
        self._io_lock = threading.Lock()
        self._io = {k: 0 if not k.endswith("seconds") else 0.0
                    for k in _IO_COUNTER_KEYS}

    # ------------------------------------------------------------------
    # Construction.
    # ------------------------------------------------------------------

    @classmethod
    def for_session(cls, session, shared_cache=None, client: str = "",
                    deadline_s: Optional[float] = None,
                    query_id: Optional[int] = None) -> "QueryContext":
        """The per-query context ``Session.execute`` builds when none was
        handed in. ``shared_cache`` (the serving frontend's cross-session
        result cache) takes precedence over the session's own; an
        explicit ``deadline_s`` (the frontend's submit-time deadline)
        over the session's ``robustness.deadlineMs`` conf; an explicit
        ``query_id`` (allocated at SUBMIT time by the frontend, so
        queue-expired cancellations correlate) over a fresh one."""
        cache = shared_cache if shared_cache is not None \
            else session.result_cache
        if deadline_s is None:
            ms = session.hs_conf.robustness_deadline_ms()
            if ms > 0:
                deadline_s = time.perf_counter() + ms / 1000.0
        return cls(session, result_cache=cache,
                   capture=session.hs_conf.advisor_capture_enabled(),
                   client=client, deadline_s=deadline_s,
                   query_id=query_id)

    @contextlib.contextmanager
    def activate(self):
        token = _CONTEXT.set(self)
        try:
            yield self
        finally:
            _CONTEXT.reset(token)

    # ------------------------------------------------------------------
    # Per-query io attribution (parallel/io.py credits the active ctx).
    # ------------------------------------------------------------------

    def note_io(self, **deltas) -> None:
        with self._io_lock:
            for k, v in deltas.items():
                if k in self._io:
                    self._io[k] += v

    def io_stats(self) -> dict:
        with self._io_lock:
            return dict(self._io)

    # ------------------------------------------------------------------
    # Executor write-backs (session stores, locked — workers share
    # sessions).
    # ------------------------------------------------------------------

    def record_join_actual(self, condition_repr: str, rows: int) -> None:
        record_join_actual(self.session, condition_repr, rows)


_JOIN_ACTUALS_MAX = 256


def _leaf_identity(leaf) -> str:
    """A leaf identity STABLE across the optimizer's own rewrites: the
    join reorderer records estimate keys BEFORE index substitution and
    partition pruning, the executors record actuals AFTER, and the two
    must pair. A Scan's ``partition_base_path`` survives ``with_files``
    (pruning replaces root_paths with the kept file list but copies the
    partition base); an IndexScan's log-entry source rootPaths are the
    original Scan's directories, abspath'd at create time. Both reduce
    a rewritten leaf to the source directory the pre-rewrite Scan
    carried."""
    rel = getattr(leaf, "relation", None)
    if rel is not None:  # Scan
        base = getattr(rel, "partition_base_path", None)
        if base:
            return str(base)
        paths = getattr(rel, "root_paths", None) or []
        return str(paths[0]) if paths else leaf.node_name
    entry = getattr(leaf, "index_entry", None)
    if entry is not None:  # IndexScan
        try:
            paths = entry.relations[0].rootPaths
            if paths:
                return str(paths[0])
        except Exception:
            pass
        return f"index:{getattr(entry, 'name', '?')}"
    return leaf.node_name


def join_side_signature(plan) -> str:
    """Order-insensitive signature of one join input: the sorted,
    rewrite-stable identities of its scan leaves."""
    try:
        leaves = plan.collect_leaves()
    except Exception:
        return getattr(plan, "node_name", "?")
    return "+".join(sorted(_leaf_identity(leaf) for leaf in leaves))


def join_actual_key(condition, left, right) -> str:
    """THE estimate/actual pairing key for one executed inner join:
    condition repr qualified by both input-side signatures, so two
    table pairs sharing a condition TEXT (``a.k = b.k`` joined from
    different sources) never collide in the bounded actuals store or in
    the adaptive correction store. Written identically by the join
    reorderer (estimates) and the staged/fused/SPMD executors
    (actuals)."""
    return (f"{condition!r} @ {join_side_signature(left)} >< "
            f"{join_side_signature(right)}")


def record_join_actual(session, condition_repr: str, rows: int) -> None:
    """Locked LRU write-back of an executed inner join's observed output
    rows onto the owning session (the ONE copy of the bound/eviction
    policy — shared by the serving QueryContext and the executor's
    contextless fallback). Keys are the composite
    :func:`join_actual_key` strings. When the adaptive feedback loop is
    on, the observation also feeds the process-wide correction store."""
    actuals = getattr(session, "_join_actuals", None)
    lock = getattr(session, "_join_actuals_lock", None)
    if actuals is None or lock is None:
        return
    with lock:
        actuals[condition_repr] = int(rows)
        actuals.move_to_end(condition_repr)
        while len(actuals) > _JOIN_ACTUALS_MAX:
            actuals.popitem(last=False)
    try:
        if session.hs_conf.adaptive_feedback_enabled():
            from ..adaptive import feedback as _feedback
            _feedback.get_store().observe(session, condition_repr,
                                          int(rows))
    except Exception:
        pass  # feedback accounting must never fail a query


def next_query_id() -> int:
    """Allocate one process-wide query id eagerly (the serving frontend
    stamps it at SUBMIT time, so events emitted before execution — the
    queue-expired cancellation — still correlate)."""
    return next(_QUERY_IDS)


def active_context() -> Optional[QueryContext]:
    """The QueryContext of the in-flight execution, if any."""
    return _CONTEXT.get()


# ---------------------------------------------------------------------------
# Cooperative per-query deadline (robustness layer).
# ---------------------------------------------------------------------------

def deadline_remaining_s() -> Optional[float]:
    """Seconds until the active query's deadline (may be negative), or
    None when no context / no deadline — the io wait loops use this to
    bound their condition waits."""
    ctx = _CONTEXT.get()
    if ctx is None or ctx.deadline_s is None:
        return None
    return ctx.deadline_s - time.perf_counter()


def check_deadline(where: str = "") -> None:
    """The cooperative cancellation point: a hard no-op (one contextvar
    read, one attribute check) unless the active query carries a
    deadline AND it has expired — then the typed QueryDeadlineError
    aborts the execution at this boundary. Instrumented at the
    executor's per-node stage entry, the pooled-read gather, the
    prefetch consumer wait, retry backoffs, and SPMD dispatch."""
    ctx = _CONTEXT.get()
    if ctx is None or ctx.deadline_s is None:
        return
    if time.perf_counter() < ctx.deadline_s:
        return
    _trip_deadline(ctx, where)


def _trip_deadline(ctx: QueryContext, where: str) -> None:
    elapsed_ms = (time.perf_counter() - ctx.created_s) * 1000.0
    with ctx._io_lock:
        first = not ctx._cancel_emitted
        ctx._cancel_emitted = True
    if first:
        try:  # trace attribution: flag the span the cancellation hit
            from ..telemetry import trace as _trace
            pair = _trace.active()
            if pair is not None and pair[1] is not None:
                pair[1].attrs["deadline_exceeded"] = True
                pair[1].attrs["cancelled_at"] = where
        except Exception:
            pass
    deadline_cancel(ctx.session, ctx.query_id, where, elapsed_ms,
                    emit=first)


def deadline_cancel(session, query_id: int, where: str,
                    elapsed_ms: float, emit: bool = True) -> None:
    """THE cancellation protocol, shared by the mid-query trip above
    and the serving frontend's queue fast-fail: bump the process
    counter, emit ONE QueryCancelledEvent (``emit=False`` on re-trips
    of an already-cancelled query), raise the typed error."""
    from ..exceptions import QueryDeadlineError
    if emit:
        from ..robustness import faults as _faults
        _faults.note(deadline_cancellations=1)
        try:
            if session is not None:
                from ..telemetry.events import QueryCancelledEvent
                from ..telemetry.logging import get_logger
                get_logger(
                    session.hs_conf.event_logger_class()
                ).log_event(QueryCancelledEvent(
                    message=(f"query {query_id} cancelled at "
                             f"{where or 'boundary'}: deadline expired "
                             f"after {elapsed_ms:.1f} ms"),
                    query_id=query_id, where=where,
                    elapsed_ms=round(elapsed_ms, 3)))
        except Exception:
            pass  # observability must never mask the cancellation
    raise QueryDeadlineError(
        f"query {query_id} exceeded its deadline "
        f"({elapsed_ms:.1f} ms elapsed; cancelled at "
        f"{where or 'stage boundary'})")
