"""Shape-class execution layer: padded length classes + compile observability.

The engine's hot-path tax on TPU is the XLA recompilation storm: every
data-dependent array length (filter survivor count, join match total, group
count, per-file row count) is a distinct static shape, and every eager jnp
primitive touching it forces a fresh trace+compile. One TPC-H q17 run was
measured at ~350 compilations (BENCH_r05) — the classic shape-instability
failure mode that makes cold/first-query latency unpredictable.

The fix implemented here: canonicalize lengths entering jitted kernels to a
GEOMETRIC LENGTH CLASS (power-of-``growthFactor`` multiples of
``minPadElements``), with an explicit valid count riding along. All
per-file / per-bucket / per-predicate invocations then collapse onto a
handful of compiled programs — one per (op, class) instead of one per
(op, exact length). Kernels guarantee byte-identical results after
unpadding; the padding/masking contract is:

- Padded rows carry arbitrary values. Any kernel consuming a padded array
  must either (a) be elementwise (garbage in the pad region stays in the pad
  region), (b) mask pads explicitly (``valid_mask``/``mask_tail``), or
  (c) route pads to a sink: sorts get a leading is-pad key so pads sort
  last; segment scatters get an out-of-range segment id (XLA drops
  out-of-bounds scatter updates); gathers use in-bounds filler indices.
- ``padded_length(n) == n`` whenever bucketing is disabled, the array is
  huge (``exactFallbackRows`` + ``maxWasteRatio``), or the input is a
  tracer (inside an outer jit the shape is already static — the SPMD path
  compiles its own fused programs and must not be re-padded).

Compile observability: a process-level counter hooked on jax.monitoring's
``/jax/core/compile/backend_compile_duration`` event (one firing per real
XLA backend compile). The executor emits the per-execution delta as a
``KernelCompileEvent``; ``explain()`` surfaces totals in its
"Compilation:" section; bench.py records per-phase counts from it.
Inside a traced query each compile is also an ``xla.compile`` span
naming the jitted function and whether the persistent compilation cache
served it.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
import threading
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..index.constants import IndexConstants
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace

# ---------------------------------------------------------------------------
# Parameters (conf-backed; see config.py shape_bucketing_* accessors).
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeParams:
    enabled: bool = \
        IndexConstants.TPU_SHAPE_BUCKETING_ENABLED_DEFAULT == "true"
    growth_factor: float = float(
        IndexConstants.TPU_SHAPE_BUCKETING_GROWTH_FACTOR_DEFAULT)
    min_pad: int = int(IndexConstants.TPU_SHAPE_BUCKETING_MIN_PAD_DEFAULT)
    max_waste_ratio: float = float(
        IndexConstants.TPU_SHAPE_BUCKETING_MAX_WASTE_RATIO_DEFAULT)
    exact_fallback_rows: int = int(
        IndexConstants.TPU_SHAPE_BUCKETING_EXACT_FALLBACK_ROWS_DEFAULT)


_DEFAULT_PARAMS = ShapeParams()
_PARAMS: contextvars.ContextVar = contextvars.ContextVar(
    "hst_shape_params", default=None)


def params_from_conf(hs_conf) -> ShapeParams:
    """Build ShapeParams from a HyperspaceConf (validated, clamped sane)."""
    growth = max(float(hs_conf.shape_bucketing_growth_factor()), 1.125)
    return ShapeParams(
        enabled=bool(hs_conf.shape_bucketing_enabled()),
        growth_factor=growth,
        min_pad=max(int(hs_conf.shape_bucketing_min_pad()), 1),
        max_waste_ratio=max(
            float(hs_conf.shape_bucketing_max_waste_ratio()), 0.0),
        exact_fallback_rows=max(
            int(hs_conf.shape_bucketing_exact_fallback_rows()), 1))


def active_params() -> ShapeParams:
    p = _PARAMS.get()
    return p if p is not None else _DEFAULT_PARAMS


@contextlib.contextmanager
def use_params(p: Optional[ShapeParams]):
    """Scope the active shape parameters (executor/actions enter this with
    the session conf; tests use it to force-enable/disable)."""
    token = _PARAMS.set(p)
    try:
        yield
    finally:
        _PARAMS.reset(token)


@contextlib.contextmanager
def use_conf(hs_conf):
    with use_params(params_from_conf(hs_conf) if hs_conf is not None
                    else None):
        yield


# ---------------------------------------------------------------------------
# Length classes.
# ---------------------------------------------------------------------------

def padded_length(n: int, params: Optional[ShapeParams] = None) -> int:
    """The geometric length class for ``n`` — the canonical padded length.

    Returns ``n`` unchanged when bucketing is disabled, ``n <= 0``, or the
    array is huge and the padding would waste more than ``max_waste_ratio``
    of its size (huge arrays amortize their own compile; the waste would be
    real HBM).
    """
    p = params if params is not None else active_params()
    if not p.enabled or n <= 0:
        return n
    c = p.min_pad
    # Geometric ladder; ceil keeps growth > 1 making progress at every rung.
    while c < n:
        c = int(math.ceil(c * p.growth_factor))
    if n >= p.exact_fallback_rows and (c - n) > p.max_waste_ratio * n:
        return n
    return c


def is_padded(arr, n: int) -> bool:
    return int(arr.shape[0]) != int(n)


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# Pad / mask / unpad primitives.
# ---------------------------------------------------------------------------

def pad_to(arr, target: int, fill=0):
    """Pad a 1-D array to ``target`` with ``fill``. Host numpy pads on host
    (no compile); device arrays go through the banked pad kernel (one
    program per (length, class, dtype) — vs one per op in the downstream
    chain — that the artifact store can persist across boots); tracers
    (SPMD prep walks) stay on the in-trace lax.pad."""
    n = int(arr.shape[0])
    if target <= n:
        return arr
    if isinstance(arr, np.ndarray):
        out = np.empty(target, dtype=arr.dtype)
        out[:n] = arr
        out[n:] = fill
        return out
    if _is_tracer(arr):
        pad_scalar = jnp.asarray(fill, arr.dtype)
        return jax.lax.pad(arr, pad_scalar, [(0, target - n, 0)])
    from ..ops import kernels
    return kernels.pad_array(arr, fill, target)


def pad_class(arr, fill=0, params: Optional[ShapeParams] = None):
    """(padded array, valid count): pad to the array's length class."""
    n = int(arr.shape[0])
    if _is_tracer(arr):
        return arr, n
    return pad_to(arr, padded_length(n, params), fill), n


def unpad(arr, n: int):
    """First ``n`` entries (the valid prefix) of a padded array."""
    if int(arr.shape[0]) == int(n):
        return arr
    if isinstance(arr, np.ndarray) or _is_tracer(arr):
        return arr[:n]
    from ..ops import kernels
    return kernels.slice_arrays((arr,), 0, int(n))[0]


def valid_mask(target: int, n: int):
    """Boolean mask: True for the valid prefix [0, n) of a class-length
    array. The comparison scalar is a runtime argument, so one compiled
    program serves every ``n`` at a given class."""
    return jnp.arange(target, dtype=jnp.int32) < jnp.int32(n)


def mask_tail(arr, n: int, fill):
    """Overwrite the pad region with ``fill`` (e.g. a searchsorted sentinel
    or an out-of-range segment id). No-op when the array is exact."""
    target = int(arr.shape[0])
    if target == int(n):
        return arr
    return jnp.where(valid_mask(target, n), arr,
                     jnp.asarray(fill, arr.dtype))


# ---------------------------------------------------------------------------
# Process-level compile counter (jax.monitoring hook).
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Recorded only when the persistent compilation cache served the
# executable, on the compiling thread, inside the compile event's span.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_counter_lock = threading.Lock()
_compile_total = 0
_compile_seconds = 0.0
_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "hst_compile_scope", default=None)
_listener_installed = False
_cache_hit = threading.local()


def _on_compile_event(event: str, duration_secs: float, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        if not _trace.idle():
            _cache_hit.seen = True
        return
    if event != _COMPILE_EVENT:
        return
    global _compile_total, _compile_seconds
    holder = _SCOPE.get()
    with _counter_lock:
        _compile_total += 1
        _compile_seconds += float(duration_secs)
        if holder is not None:
            holder["count"] += 1
            holder["seconds"] += float(duration_secs)
    if not _trace.idle():
        _record_compile_span(kw.get("fun_name", ""), float(duration_secs))


def _record_compile_span(fun_name: str, seconds: float) -> None:
    """One ``xla.compile`` span for the compile that just ended on this
    thread, placed back over the time it took."""
    end = time.perf_counter()
    cached = bool(getattr(_cache_hit, "seen", False))
    _cache_hit.seen = False
    span = _trace.add_span(SN.XLA_COMPILE, end - seconds, node=fun_name,
                           fun_name=fun_name, seconds=seconds, cached=cached)
    if span is not None:
        span.end_perf = end


def install_compile_counter() -> None:
    """Register the monitoring listener once per process (idempotent).
    The claim-then-register dance runs under the counter lock: two
    threads racing the unguarded flag would BOTH register the listener
    and double-count every compile from then on (HS301)."""
    global _listener_installed
    with _counter_lock:
        if _listener_installed:
            return
        _listener_installed = True
    try:
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
    except Exception:  # very old jax without monitoring: counter stays 0
        with _counter_lock:
            _listener_installed = False


def compile_count() -> int:
    install_compile_counter()
    return _compile_total


def compile_seconds() -> float:
    install_compile_counter()
    return _compile_seconds


@contextlib.contextmanager
def compile_scope():
    """Tally the compiles fired inside the scope (the executor wraps plan
    execution). Yields a holder dict whose ``count``/``seconds`` tally
    only THIS context's compiles — the contextvar keeps concurrent
    serving executions from reading each other's deltas off the
    process-global counter."""
    install_compile_counter()
    holder = {"count": 0, "seconds": 0.0}
    token = _SCOPE.set(holder)
    try:
        yield holder
    finally:
        _SCOPE.reset(token)


install_compile_counter()
