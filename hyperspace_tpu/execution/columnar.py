"""Device-resident columnar tables.

This is the TPU-native data representation the whole engine computes over:
every column is a fixed-width JAX array in HBM. Variable-length strings are
dictionary-encoded **order-preserving** at the host→device boundary (codes
compare like the strings they stand for, so range predicates and sorts work
directly on codes — SURVEY §7 hard-part #2). Dates are int32 days; decimals
become float64.

Host↔device crossings happen only at parquet read/write and at collect().
"""

from __future__ import annotations

import datetime
import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from ..exceptions import HyperspaceException
from ..schema import BOOL, DATE, FLOAT32, FLOAT64, INT32, INT64, STRING, Field, Schema
from ..telemetry import span_names as SN
from ..telemetry import trace as _trace

_DEVICE_DTYPE = {
    INT32: jnp.int32,
    INT64: jnp.int64,
    FLOAT32: jnp.float32,
    FLOAT64: jnp.float64,
    BOOL: jnp.bool_,
    DATE: jnp.int32,
    STRING: jnp.int32,  # dictionary codes.
}


@dataclass
class Column:
    """One device column: values (or dictionary codes) + optional validity."""

    dtype: str  # logical type name from schema.py
    data: jax.Array
    validity: Optional[jax.Array] = None  # bool, True = valid; None = all valid
    dictionary: Optional[np.ndarray] = None  # sorted unique strings (host)

    def __post_init__(self):
        if self.dtype == STRING and self.dictionary is None:
            raise HyperspaceException("STRING columns require a dictionary")

    def __len__(self):
        return int(self.data.shape[0])

    @property
    def has_nulls(self) -> bool:
        return self.validity is not None

    def take(self, indices) -> "Column":
        # clip mode: padded gather indices (shape-class execution) may
        # carry out-of-range filler in the pad tail; clipping keeps the
        # gather defined (the clipped rows land in the pad region of the
        # result and are never read as data).
        return Column(self.dtype, jnp.take(self.data, indices, axis=0,
                                           mode="clip"),
                      None if self.validity is None
                      else jnp.take(self.validity, indices, axis=0,
                                    mode="clip"),
                      self.dictionary)

    def slice(self, start: int, stop: int) -> "Column":
        return Column(self.dtype, self.data[start:stop],
                      None if self.validity is None else self.validity[start:stop],
                      self.dictionary)


@dataclass
class Table:
    """An ordered set of equal-length device columns.

    ``bucket_order`` is a physical-layout hint: ``(num_buckets, key_cols)``
    means rows are grouped by ascending bucket id (hash of key_cols) and
    sorted by key_cols within each bucket — the covering-index invariant.
    The join path uses it to skip re-sorting (shuffle-free SMJ analogue).
    Operations that permute or merge rows must drop it.

    ``valid_rows`` is the shape-class execution contract
    (execution/shapes.py): when set, the column arrays are padded to a
    length class and only rows ``[0, valid_rows)`` are data — the pad tail
    holds arbitrary values that must never be read. ``num_rows`` is the
    LOGICAL count; ``data_rows`` the physical array length. Everything
    leaving the engine (to_arrow/to_host/compact) drops the padding, so
    results are byte-identical to exact-shape execution.
    """

    columns: Dict[str, Column]
    bucket_order: Optional[Tuple[int, Tuple[str, ...]]] = None
    valid_rows: Optional[int] = None

    def __post_init__(self):
        lengths = {len(c) for c in self.columns.values()}
        if len(lengths) > 1:
            raise HyperspaceException(f"Ragged table: column lengths {lengths}")
        if self.valid_rows is not None:
            phys = next(iter(lengths), 0)
            if not 0 <= self.valid_rows <= phys:
                raise HyperspaceException(
                    f"valid_rows {self.valid_rows} outside [0, {phys}]")
            if self.valid_rows == phys:
                self.valid_rows = None  # exact: no padding in play

    @property
    def num_rows(self) -> int:
        if self.valid_rows is not None:
            return self.valid_rows
        if not self.columns:
            return 0
        return len(next(iter(self.columns.values())))

    @property
    def data_rows(self) -> int:
        """Physical column length (== num_rows unless class-padded)."""
        if not self.columns:
            return 0
        return len(next(iter(self.columns.values())))

    @property
    def is_padded(self) -> bool:
        return self.valid_rows is not None

    def compact(self) -> "Table":
        """Drop class padding: slice every column to the valid prefix
        (one fused program per (table signature, valid count) — a
        data-dependent count compiles per value, so terminal results
        prefer the free host-boundary trim in executor.execute). No-op
        (and no copy) for exact tables."""
        n = self.valid_rows
        if n is None:
            return self
        return self.slice(0, n)

    @property
    def names(self) -> List[str]:
        return list(self.columns.keys())

    def column(self, name: str) -> Column:
        if name not in self.columns:
            raise HyperspaceException(
                f"Unknown column '{name}'; available: {self.names}")
        return self.columns[name]

    def schema(self) -> Schema:
        return Schema([Field(n, c.dtype, c.has_nulls)
                       for n, c in self.columns.items()])

    def _keep_order(self, names: Sequence[str]) -> Optional[Tuple]:
        if self.bucket_order and all(k in names for k in self.bucket_order[1]):
            return self.bucket_order
        return None

    def select(self, names: Sequence[str]) -> "Table":
        return Table({n: self.column(n) for n in names},
                     bucket_order=self._keep_order(names),
                     valid_rows=self.valid_rows)

    def take(self, indices, valid_rows: Optional[int] = None) -> "Table":
        """Row gather. ``valid_rows`` declares the valid prefix of a
        class-padded ``indices`` array (shape-class execution). All
        column buffers gather through ONE fused program
        (kernels.gather_arrays) — one compile per table signature."""
        from ..ops import kernels
        arrays, spec = [], []
        for n, c in self.columns.items():
            arrays.append(c.data)
            spec.append((n, "d"))
            if c.validity is not None:
                arrays.append(c.validity)
                spec.append((n, "v"))
        taken = dict(zip(spec, kernels.gather_arrays(indices, arrays)))
        return Table({n: Column(c.dtype, taken[(n, "d")],
                                taken.get((n, "v")), c.dictionary)
                      for n, c in self.columns.items()},
                     valid_rows=valid_rows)

    def filter(self, mask, padded: bool = False) -> "Table":
        # A subsequence of bucket-ordered rows is still bucket-ordered.
        # One flatnonzero for the whole table: per-column boolean indexing
        # would re-run the mask→indices conversion for every column (and
        # jax's bool-index path is markedly slower than an int gather).
        # Shape classes: the survivor count is data-dependent — the classic
        # recompile driver — so with ``padded=True`` (the executor's hot
        # path) the gather indices are padded to their length class and the
        # result rides with valid_rows. Default stays exact: callers
        # outside the padded pipeline (SPMD routing, build, chunk streams)
        # read .data directly and must keep exact shapes.
        if mask.shape[0] != self.data_rows:
            # jnp.take clips out-of-range indices silently; fail loud here.
            raise HyperspaceException(
                f"filter mask length {mask.shape[0]} != rows {self.data_rows}")
        idx, m = filter_indices(mask, self.valid_rows, padded=padded)
        out = self.take(idx, valid_rows=m if int(idx.shape[0]) != m else None)
        return Table(out.columns, bucket_order=self.bucket_order,
                     valid_rows=out.valid_rows)

    def slice(self, start: int, stop: int) -> "Table":
        # start/stop address the valid prefix, so the result is exact.
        # Device-resident buffers slice through ONE fused program per
        # table signature; host (numpy) buffers slice for free.
        from ..ops import kernels
        dev, spec = [], []
        for n, c in self.columns.items():
            if not isinstance(c.data, np.ndarray):
                dev.append(c.data)
                spec.append((n, "d"))
            if c.validity is not None and not isinstance(c.validity,
                                                         np.ndarray):
                dev.append(c.validity)
                spec.append((n, "v"))
        sliced = dict(zip(spec, kernels.slice_arrays(dev, start, stop))) \
            if dev else {}

        def part(c, name, kind, host):
            if (name, kind) in sliced:
                return sliced[(name, kind)]
            return host[start:stop]

        return Table({n: Column(c.dtype, part(c, n, "d", c.data),
                                part(c, n, "v", c.validity)
                                if c.validity is not None else None,
                                c.dictionary)
                      for n, c in self.columns.items()},
                     bucket_order=self.bucket_order)

    def with_column(self, name: str, col: Column) -> "Table":
        out = dict(self.columns)
        out[name] = col
        return Table(out, bucket_order=self.bucket_order,
                     valid_rows=self.valid_rows)

    def to_host(self) -> "Table":
        """Materialize every column as host numpy with ONE device_get over
        the whole pytree. On a remote-attached TPU the per-transfer round
        trip (not bandwidth) dominates, so anything that will be sliced
        many times on the host (e.g. one parquet file per bucket) must be
        fetched wholesale first, never slice-by-slice. Class padding is
        dropped on the host (free — a numpy slice, no device program)."""
        import jax
        arrays = {}
        for n, c in self.columns.items():
            arrays[(n, "d")] = c.data
            if c.validity is not None:
                arrays[(n, "v")] = c.validity
        with _trace.span(SN.DEVICE_SYNC, node="to_host"):
            host = jax.device_get(arrays)
        rows = self.num_rows

        def trim(a):
            a = np.asarray(a)
            return a[:rows] if self.valid_rows is not None else a

        return Table({n: Column(c.dtype, trim(host[(n, "d")]),
                                trim(host[(n, "v")])
                                if c.validity is not None else None,
                                c.dictionary)
                      for n, c in self.columns.items()},
                     bucket_order=self.bucket_order)

    def rename(self, mapping: Dict[str, str]) -> "Table":
        order = self.bucket_order
        if order:
            order = (order[0], tuple(mapping.get(k, k) for k in order[1]))
        return Table({mapping.get(n, n): c for n, c in self.columns.items()},
                     bucket_order=order, valid_rows=self.valid_rows)

    @staticmethod
    def concat(tables: Sequence["Table"]) -> "Table":
        """Union of schema-aligned tables; string dictionaries are re-unified.
        Class-padded inputs are compacted first (an interleaved pad tail
        cannot ride through a concatenation)."""
        tables = [t.compact() for t in tables]
        tables = [t for t in tables if t.num_rows > 0] or list(tables[:1])
        if len(tables) == 1:
            return tables[0]
        first = tables[0]
        out: Dict[str, Column] = {}
        for name in first.names:
            cols = [t.column(name) for t in tables]
            dtype = cols[0].dtype
            if any(c.dtype != dtype for c in cols):
                raise HyperspaceException(f"concat dtype mismatch on '{name}'")
            if dtype == STRING:
                out[name] = _concat_string_columns(cols)
            else:
                data = jnp.concatenate([c.data for c in cols])
                validity = None
                if any(c.validity is not None for c in cols):
                    validity = jnp.concatenate([
                        c.validity if c.validity is not None
                        else jnp.ones(len(c), dtype=jnp.bool_) for c in cols])
                out[name] = Column(dtype, data, validity)
        return Table(out)

    # ------------------------------------------------------------------
    # Host boundary.
    # ------------------------------------------------------------------

    def to_arrow(self) -> pa.Table:
        # ONE batched device_get for every device-resident buffer (data +
        # validity across all columns): each device_get is a host↔device
        # round trip, so per-column fetches made a 4-column result cost 8
        # round trips. Host-resident columns (e.g. after
        # to_host()) skip the transfer entirely.
        device_buffers = {}
        for name, col in self.columns.items():
            if not isinstance(col.data, np.ndarray):
                device_buffers[(name, "d")] = col.data
            if col.validity is not None and \
                    not isinstance(col.validity, np.ndarray):
                device_buffers[(name, "v")] = col.validity
        fetched = {}
        if device_buffers:
            with _trace.span(SN.DEVICE_SYNC, node="result"):
                fetched = jax.device_get(device_buffers)

        def fetch(a, key):
            if key in fetched:
                return np.asarray(fetched[key])
            return a

        arrays = []
        rows = self.num_rows
        for name, col in self.columns.items():
            np_data = fetch(col.data, (name, "d"))
            np_valid = (fetch(col.validity, (name, "v"))
                        if col.validity is not None else None)
            if self.valid_rows is not None:
                # Drop class padding at the host boundary (a numpy slice —
                # no device program, byte-identical to exact execution).
                np_data = np.asarray(np_data)[:rows]
                if np_valid is not None:
                    np_valid = np.asarray(np_valid)[:rows]
            mask = None if np_valid is None else ~np_valid
            if col.dtype == STRING:
                codes = np_data
                safe = np.where(codes >= 0, codes, 0)
                values = col.dictionary[safe] if len(col.dictionary) else \
                    np.array([""] * len(codes), dtype=object)
                arr = pa.array(values, type=pa.string(),
                               mask=mask if mask is not None else (codes < 0))
            elif col.dtype == DATE:
                arr = pa.array(np_data.astype("int32"), type=pa.int32(), mask=mask)
                arr = arr.cast(pa.date32())
            elif col.dtype == BOOL:
                arr = pa.array(np_data.astype(bool), mask=mask)
            else:
                arr = pa.array(np_data, mask=mask)
            arrays.append((name, arr))
        return pa.table(dict(arrays))

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    @staticmethod
    def from_arrow(table: pa.Table, pad_to_class: bool = False) -> "Table":
        # Struct columns are flattened into dotted leaf names ("a.b.c") so
        # only fixed-width flat arrays reach the device (see
        # Schema.from_arrow).
        while any(pa.types.is_struct(f.type) for f in table.schema):
            table = table.flatten()
        # Shape classes at the host->device boundary: padding in numpy is
        # FREE (no device program), so executor-bound reads land on their
        # length class before any XLA op ever sees the exact row count.
        target = None
        if pad_to_class and table.num_rows > 0:
            from . import shapes
            cls = shapes.padded_length(table.num_rows)
            if cls != table.num_rows:
                target = cls
        cols: Dict[str, Column] = {}
        for name in table.column_names:
            cols[name] = _encode_arrow_column(table.column(name), target)
        return Table(cols, valid_rows=table.num_rows
                     if target is not None else None)


def filter_indices(mask, valid_rows: Optional[int] = None,
                   padded: bool = True):
    """(gather indices, survivor count) for a keep mask over a possibly
    class-padded table. Pad rows are masked out; with ``padded`` the
    indices come out at the survivor count's length class directly
    (jnp.nonzero with a static class size, filler 0 — always in-bounds
    for a non-empty source): no exact-length array ever materializes, so
    downstream gathers compile once per class instead of once per
    survivor count."""
    from ..ops import kernels
    return kernels.mask_count_nonzero(mask, valid_rows, padded)


def pad_table_to_class(table: Table) -> Table:
    """Class-pad an exact table (one lax.pad per column buffer — a few
    tiny programs per distinct table length, vs one per downstream op).
    The executor applies this at scan boundaries so every chain over the
    table runs at its length class."""
    from . import shapes
    n = table.num_rows
    if table.is_padded or n == 0:
        return table
    cls = shapes.padded_length(n)
    if cls == n:
        return table
    cols = {}
    for name, c in table.columns.items():
        if isinstance(c.data, np.ndarray):
            return table  # host-resident tables stay exact
        cols[name] = Column(c.dtype, shapes.pad_to(c.data, cls),
                            shapes.pad_to(c.validity, cls, False)
                            if c.validity is not None else None,
                            c.dictionary)
    return Table(cols, bucket_order=table.bucket_order, valid_rows=n)


# ---------------------------------------------------------------------------
# Encoding.
# ---------------------------------------------------------------------------

def _pad_host(np_data: np.ndarray, target: Optional[int], fill=0) -> np.ndarray:
    """Host-side class pad (no device program; see Table.from_arrow)."""
    if target is None or np_data.shape[0] >= target:
        return np_data
    out = np.empty(target, dtype=np_data.dtype)
    out[:np_data.shape[0]] = np_data
    out[np_data.shape[0]:] = fill
    return out


def _encode_arrow_column(chunked: pa.ChunkedArray,
                         target: Optional[int] = None) -> Column:
    t = chunked.type
    if pa.types.is_dictionary(t):
        chunked = chunked.cast(t.value_type)
        t = t.value_type
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return _encode_string(chunked, target)
    combined = chunked.combine_chunks() if chunked.num_chunks != 1 else chunked.chunk(0)
    null_count = combined.null_count
    if pa.types.is_date32(t):
        np_data = combined.cast(pa.int32()).to_numpy(zero_copy_only=False)
        dtype = DATE
    elif pa.types.is_decimal(t):
        np_data = combined.cast(pa.float64()).to_numpy(zero_copy_only=False)
        dtype = FLOAT64
    elif pa.types.is_timestamp(t):
        np_data = combined.cast(pa.int64()).to_numpy(zero_copy_only=False)
        dtype = INT64
    elif pa.types.is_boolean(t):
        np_data = combined.to_numpy(zero_copy_only=False)
        dtype = BOOL
    elif pa.types.is_integer(t):
        # fill_null BEFORE to_numpy: the null path otherwise round-trips
        # through float64 (NaN-null), silently corrupting int64 values
        # beyond ±2^53. Validity masks the filled zeros below.
        filled = combined.fill_null(0) if null_count else combined
        wide = filled.cast(pa.int64()).to_numpy(zero_copy_only=False)
        if t.bit_width <= 32:
            np_data, dtype = wide.astype(np.int32), INT32
        else:
            np_data, dtype = wide, INT64
    elif pa.types.is_floating(t):
        np_data = combined.to_numpy(zero_copy_only=False)
        dtype = FLOAT32 if t.bit_width == 32 else FLOAT64
    else:
        raise HyperspaceException(f"Unsupported arrow type: {t}")

    validity = None
    if null_count:
        valid_np = ~np.asarray(combined.is_null())
        fill = 0
        np_data = np.where(valid_np, np.nan_to_num(np_data, nan=fill)
                           if np_data.dtype.kind == "f" else np_data, fill)
        validity = jnp.asarray(_pad_host(valid_np, target, False))
    dev_dtype = _DEVICE_DTYPE[dtype]
    np_data = _pad_host(np.ascontiguousarray(np_data), target)
    return Column(dtype, jnp.asarray(np_data, dtype=dev_dtype), validity)


def _encode_string(chunked: pa.ChunkedArray,
                   target: Optional[int] = None) -> Column:
    """Order-preserving dictionary encoding: codes sort like the strings."""
    combined = chunked.combine_chunks() if chunked.num_chunks != 1 else chunked.chunk(0)
    uniques = pc.unique(combined.drop_null())
    dictionary = np.sort(np.asarray(uniques).astype(str)) if len(uniques) else \
        np.array([], dtype=str)
    values = np.asarray(combined.fill_null("")).astype(str)
    codes = np.searchsorted(dictionary, values).astype(np.int32) \
        if len(dictionary) else np.zeros(len(values), np.int32)
    validity = None
    if combined.null_count:
        valid_np = ~np.asarray(combined.is_null())
        codes = np.where(valid_np, codes, -1).astype(np.int32)
        validity = jnp.asarray(_pad_host(valid_np, target, False))
    return Column(STRING, jnp.asarray(_pad_host(codes, target)), validity,
                  dictionary)


def _concat_string_columns(cols: List[Column]) -> Column:
    """Re-unify dictionaries so codes stay order-preserving across parts."""
    merged = np.unique(np.concatenate([c.dictionary for c in cols])) \
        if any(len(c.dictionary) for c in cols) else np.array([], dtype=str)
    datas, validities, any_valid = [], [], False
    for c in cols:
        remap = np.searchsorted(merged, c.dictionary).astype(np.int32) \
            if len(c.dictionary) else np.zeros(0, np.int32)
        remap_dev = jnp.asarray(remap)
        codes = jnp.where(c.data >= 0,
                          jnp.take(remap_dev, jnp.maximum(c.data, 0)), -1) \
            if len(remap) else c.data
        datas.append(codes)
        v = c.validity if c.validity is not None else jnp.ones(len(c), jnp.bool_)
        validities.append(v)
        any_valid = any_valid or c.validity is not None
    data = jnp.concatenate(datas)
    validity = jnp.concatenate(validities) if any_valid else None
    return Column(STRING, data, validity, merged)


# ---------------------------------------------------------------------------
# Parquet IO.
# ---------------------------------------------------------------------------

def _resolve_files(files: Sequence[str]):
    """(filesystem-or-None, normalized paths) — the multi-path form of
    data_store.fs_and_path, delegating to the same store resolution."""
    if not files:
        return None, list(files)
    from ..index import data_store
    store = data_store.store_for_path(files[0])
    if store is None:
        return None, list(files)
    return store.filesystem(), [store.normalize(f) for f in files]


def _file_size_weight(fs):
    """Per-file byte-weight estimator for the reader pool's in-flight
    budget (decoded size ≈ file size to first order; 0 = unweighted)."""
    import os

    def weight(path) -> int:
        try:
            if fs is None:
                return int(os.path.getsize(path))
            return int(fs.get_file_info(path).size or 0)
        except Exception:
            return 0
    return weight


def _read_parquet_pooled(files, read_cols, filters, fs) -> pa.Table:
    """Multi-file parquet read fanned out over the shared reader pool
    (parallel/io.py): the file list splits into one CONTIGUOUS sublist
    per pool thread, each task runs the fast dataset path single-threaded
    (our pool IS the parallelism — nesting pyarrow's own pool under it
    only oversubscribes), and the ordered concat keeps file order, so the
    result is byte-identical to the sequential bulk read. Sublists, not
    per-file tasks: they amortize the per-call dataset setup a per-file
    fan-out pays N times (measured 3x the bulk read's cost that way).

    ``io.enabled=false`` restores the exact legacy bulk read (pyarrow's
    native threading); ``io.threads=1`` is the strict sequential
    baseline (single-threaded bulk read) the bench A/B and determinism
    tests compare against."""
    from ..parallel import io as pio
    p = pio.active_params()
    n = p.resolved_threads()
    if not p.enabled:
        return pq.read_table(list(files), columns=read_cols,
                             filters=filters, filesystem=fs)
    if len(files) > 1 and n > 1 and not pio.in_worker():
        step = (len(files) + n - 1) // n
        groups = [files[i:i + step] for i in range(0, len(files), step)]
        fweight = _file_size_weight(fs)
        parts = pio.map_ordered(
            lambda g: pq.read_table(list(g), columns=read_cols,
                                    filters=filters, filesystem=fs,
                                    use_threads=False),
            groups, weight=lambda g: sum(fweight(f) for f in g),
            params=p, label="read_parquet")
        try:
            return pa.concat_tables(parts)
        except (pa.ArrowInvalid, pa.ArrowTypeError):
            # Heterogeneous per-file schemas: unification is the bulk
            # dataset reader's job.
            pass
    return pq.read_table(list(files), columns=read_cols, filters=filters,
                         filesystem=fs,
                         use_threads=n > 1 and not pio.in_worker())


def read_parquet(files: Sequence[str], columns: Optional[Sequence[str]] = None,
                 fmt: str = "parquet", filters=None,
                 pad_to_class: bool = False, pool: bool = True) -> Table:
    """``pad_to_class`` class-pads the result host-side (free) for the
    executor's shape-class pipeline; leave False for callers that read
    ``.data`` directly (builds, sketches, spmd leaves). Multi-file reads
    of every format fan out per file over the shared reader pool
    (parallel/io.py) with order-preserving gather; device encoding stays
    on the calling thread.

    Class-padded parquet reads route through the tiered buffer pool
    (execution/buffer_pool.py) keyed by file signature + column set +
    pruning filter: a warm probe serves the decoded padded table with
    ZERO file reads and ZERO host→device transfers; a miss decodes here
    (the pooled fan-out readers are the pool's producers) and admits the
    result. ``pool=False`` opts a caller out (the index-scan path has
    its own pool view and must not double-store)."""
    from ..parallel import io as pio
    from ..robustness import fault_names as _fn
    from ..robustness import faults as _faults
    from . import buffer_pool as _bp
    if not files:
        raise HyperspaceException("read_parquet: no files")
    # Robustness fault point: the scan-decode boundary every format
    # funnels through (hard no-op disarmed; see robustness/faults.py).
    # Fires BEFORE the pool probe so fault semantics are identical
    # pool-on vs pool-off.
    _faults.fault_point(_fn.SCAN_PARQUET_DECODE)
    pool_key = None
    if fmt == "parquet" and pad_to_class and pool and _bp.enabled():
        pool_key = _bp.scan_key(files, columns, filters)
        if pool_key is not None:
            cached = _bp.get_pool().get(pool_key)
            if cached is not None:
                return cached
    if fmt == "parquet":
        fs, files = _resolve_files(files)
        read_cols = list(columns) if columns else None
        flatten_select = None
        if columns:
            top_level = set(pq.read_schema(files[0], filesystem=fs).names)
            if any(c not in top_level for c in columns):
                # Dotted struct leaves: read each leaf's root struct column,
                # flatten after read, then select the exact leaves (pyarrow's
                # columns= would select nested leaves but rename them to the
                # leaf's own name, losing the dotted path).
                roots = []
                for c in columns:
                    root = c if c in top_level else c.split(".", 1)[0]
                    if root not in roots:
                        roots.append(root)
                read_cols, flatten_select = roots, list(columns)
        at = _read_parquet_pooled(files, read_cols, filters, fs)
        if flatten_select is not None:
            while any(pa.types.is_struct(f.type) for f in at.schema):
                at = at.flatten()
            at = at.select(flatten_select)
    elif fmt == "csv":
        import pyarrow.csv as pa_csv

        def _read_csv(f):
            # Workers parse single-threaded: the pool is the parallelism
            # (nesting pyarrow's own pool oversubscribes); the sequential
            # path keeps pyarrow's default threading like the legacy loop.
            if pio.in_worker():
                return pa_csv.read_csv(f, read_options=pa_csv.ReadOptions(
                    use_threads=False))
            return pa_csv.read_csv(f)

        tables = pio.map_ordered(_read_csv, files,
                                 weight=_file_size_weight(None),
                                 label="read_csv")
        at = pa.concat_tables(tables)
        if columns:
            at = at.select(list(columns))
    elif fmt == "avro":
        from ..util.avro import read_avro
        tables = pio.map_ordered(
            lambda f: read_avro(f, list(columns) if columns else None),
            files, weight=_file_size_weight(None), label="read_avro")
        at = pa.concat_tables(tables)
    elif fmt == "json":
        # Newline-delimited JSON (the reference's spark json source shape,
        # DefaultFileBasedSource.scala:37-44).
        import pyarrow.json as pa_json

        def _read_json(f):
            if pio.in_worker():
                return pa_json.read_json(
                    f, read_options=pa_json.ReadOptions(use_threads=False))
            return pa_json.read_json(f)

        tables = pio.map_ordered(_read_json, files,
                                 weight=_file_size_weight(None),
                                 label="read_json")
        at = pa.concat_tables(tables)
        if columns:
            at = at.select(list(columns))
    elif fmt == "orc":
        import pyarrow.orc as pa_orc
        tables = pio.map_ordered(
            lambda f: pa_orc.ORCFile(f).read(
                columns=list(columns) if columns else None),
            files, weight=_file_size_weight(None), label="read_orc")
        at = pa.concat_tables(tables)
    elif fmt == "text":
        # Spark text-source semantics: one string column "value" per line.
        # Hadoop's LineReader treats \n, \r, and \r\n all as line
        # terminators (but NOT \x0b/\x0c etc., so str.splitlines would
        # silently diverge from the reference).
        import re

        def _read_text(f):
            with open(f, encoding="utf-8", newline="") as fh:
                body = fh.read()
            lines_ = re.split("\r\n|\r|\n", body)
            if lines_ and lines_[-1] == "":
                lines_.pop()  # trailing terminator, not an empty last line
            return pa.array(lines_, type=pa.string())

        arrays = pio.map_ordered(_read_text, files,
                                 weight=_file_size_weight(None),
                                 label="read_text")
        at = pa.table({"value": pa.concat_arrays(arrays)})
        if columns:
            at = at.select(list(columns))
    else:
        raise HyperspaceException(f"Unsupported format: {fmt}")
    table = Table.from_arrow(at, pad_to_class=pad_to_class)
    if pool_key is not None:
        _bp.get_pool().put(pool_key, table)
    return table


@functools.lru_cache(maxsize=65536)
def _file_row_count(path: str, size: int, mtime_ns: int) -> int:
    fs, paths = _resolve_files([path])
    return pq.ParquetFile(paths[0], filesystem=fs).metadata.num_rows


def parquet_row_counts(files: Sequence[str]) -> List[int]:
    """Row count per file from parquet footers (no data read). Memoized
    per (path, size, mtime): budget checks run on every filtered scan,
    and re-opening every footer per query would tax the hot cached path
    (index files are immutable, so staleness means a new path/version)."""
    import os

    from ..index import data_store
    out = []
    for f in files:
        store = data_store.store_for_path(f)
        if store is None:
            st = os.stat(f)
            out.append(_file_row_count(f, st.st_size, st.st_mtime_ns))
        else:
            _, size, mtime_ms = store.file_info(f)
            out.append(_file_row_count(f, size, mtime_ms))
    return out


def _table_nbytes_estimate(obj) -> int:
    """In-flight byte estimate for a chunk (Table or (Table, provenance))
    crossing the prefetch queue — device buffer sizes, host-visible."""
    t = obj[0] if isinstance(obj, tuple) else obj
    total = 0
    for c in t.columns.values():
        total += int(getattr(c.data, "nbytes", 0) or 0)
        if c.validity is not None:
            total += int(getattr(c.validity, "nbytes", 0) or 0)
    return total


def iter_parquet_chunks(files: Sequence[str], columns: Optional[Sequence[str]],
                        chunk_rows: int):
    """Stream files as device Tables of ≤ ``chunk_rows`` rows each, yielding
    ``(table, [(file_index, rows_from_that_file), ...])`` so callers can
    attribute rows to source files (lineage). Row groups are the streaming
    unit, which is what bounds the HBM footprint for data larger than
    device memory (SURVEY §7 hard-part #1): at most ``prefetchDepth``
    buffered chunks (further capped by ``maxInflightBytes`` of decoded
    bytes) + one in production + one at the consumer are resident, the
    parallel-io prefetcher decoding chunk k+1 while chunk k computes.
    Order and provenance are exactly the sequential stream's."""
    from ..parallel import io as pio
    return pio.prefetch_iter(
        _iter_parquet_chunks(files, columns, chunk_rows),
        nbytes=_table_nbytes_estimate, label="parquet_chunks")


def _iter_parquet_chunks(files: Sequence[str],
                         columns: Optional[Sequence[str]], chunk_rows: int):
    batch: List[pa.Table] = []
    batch_rows = 0
    provenance: List[Tuple[int, int]] = []

    def flush():
        nonlocal batch, batch_rows, provenance
        if not batch:
            return None
        at = pa.concat_tables(batch) if len(batch) > 1 else batch[0]
        out = (Table.from_arrow(at), provenance)
        batch, batch_rows, provenance = [], 0, []
        return out

    fs, files = _resolve_files(list(files))
    read_cols = list(columns) if columns else None
    for fi, path in enumerate(files):
        pf = pq.ParquetFile(path, filesystem=fs)
        for rg in range(pf.num_row_groups):
            t = pf.read_row_group(rg, columns=read_cols)
            start = 0
            while start < t.num_rows:
                take = min(t.num_rows - start, chunk_rows - batch_rows)
                batch.append(t.slice(start, take))
                if provenance and provenance[-1][0] == fi:
                    provenance[-1] = (fi, provenance[-1][1] + take)
                else:
                    provenance.append((fi, take))
                batch_rows += take
                start += take
                if batch_rows >= chunk_rows:
                    yield flush()
    tail = flush()
    if tail is not None:
        yield tail


def iter_dataset_chunks(files: Sequence[str],
                        columns: Optional[Sequence[str]], chunk_rows: int,
                        filters=None):
    """Stream files as device Tables of ≤ ``chunk_rows`` rows with parquet
    predicate pushdown: row groups whose statistics exclude the filter are
    never decoded (the scan-side counterpart of iter_parquet_chunks, which
    the build uses for its lineage provenance). Depth-N prefetching
    (parallel/io.py): chunk k+1 decodes to device while the consumer
    executes chunk k.

    Streams up to ``bufferPool.streamAdmitBytes`` route through the
    tiered buffer pool: a warm probe replays the exact chunk sequence
    (byte-identical, chunk-for-chunk) with zero file reads; a miss
    streams normally while collecting chunks, admitting the sequence
    only after NORMAL exhaustion (abandoned iterations never admit a
    truncated stream). Chunk payloads are device-resident — the entries
    are device-only: evicted by dropping, never demoted."""
    from ..parallel import io as pio
    from . import buffer_pool as _bp
    pool_key = None
    if _bp.enabled():
        pool_key = _bp.stream_key(files, columns, filters, chunk_rows)
        if pool_key is not None:
            cached = _bp.get_pool().get(pool_key)
            if cached is not None:
                return iter(list(cached))
    source = _iter_dataset_chunks(files, columns, chunk_rows, filters)
    if pool_key is not None:
        source = _collect_stream(pool_key, source,
                                 _bp.stream_admit_bytes())
    return pio.prefetch_iter(source, nbytes=_table_nbytes_estimate,
                             label="dataset_chunks")


def _collect_stream(pool_key, source, admit_bytes: int):
    """Pass chunks through while accumulating them for pool admission;
    over-budget streams stop collecting (too big to replay), and only a
    NORMALLY exhausted stream admits — a consumer that abandons the
    iterator early (GeneratorExit) must never poison the pool with a
    truncated sequence."""
    from . import buffer_pool as _bp
    chunks: List[Table] = []
    total = 0
    for chunk in source:
        if chunks is not None:
            total += _table_nbytes_estimate(chunk)
            if total > admit_bytes:
                chunks = None
            else:
                chunks.append(chunk)
        yield chunk
    if chunks is not None:
        _bp.get_pool().put(pool_key, chunks, nbytes=total,
                           device_only=True)


def _iter_dataset_chunks(files: Sequence[str],
                         columns: Optional[Sequence[str]], chunk_rows: int,
                         filters=None):
    import pyarrow.dataset as pa_ds

    expr = pq.filters_to_expression(filters) if filters is not None else None
    fs, files = _resolve_files(list(files))
    ds = pa_ds.dataset(list(files), format="parquet", filesystem=fs)
    batch: List[pa.Table] = []
    batch_rows = 0
    for rb in ds.scanner(columns=list(columns) if columns else None,
                         filter=expr,
                         batch_size=max(chunk_rows, 1)).to_batches():
        if rb.num_rows == 0:
            continue
        t = pa.Table.from_batches([rb])
        start = 0
        while start < t.num_rows:
            take = min(t.num_rows - start, chunk_rows - batch_rows)
            batch.append(t.slice(start, take))
            batch_rows += take
            start += take
            if batch_rows >= chunk_rows:
                yield Table.from_arrow(pa.concat_tables(batch))
                batch, batch_rows = [], 0
    if batch:
        yield Table.from_arrow(pa.concat_tables(batch))


def write_parquet(table: Table, path: str, row_group_size: Optional[int] = None) -> None:
    fs, paths = _resolve_files([path])
    pq.write_table(table.to_arrow(), paths[0],
                   row_group_size=row_group_size, filesystem=fs)


def empty_table(schema: "Schema") -> Table:
    cols = {}
    for f in schema.fields:
        dictionary = np.array([], dtype=str) if f.dtype == STRING else None
        cols[f.name] = Column(f.dtype,
                              jnp.zeros(0, _DEVICE_DTYPE[f.dtype]),
                              None, dictionary)
    return Table(cols)


def dictionaries_equal(a: Optional[np.ndarray], b: Optional[np.ndarray]) -> bool:
    return a is b or (a is not None and b is not None
                      and len(a) == len(b) and bool(np.array_equal(a, b)))


def translate_codes(target_dictionary: np.ndarray, col: Column):
    """Re-map a STRING column's codes into ``target_dictionary``'s code space.

    Strings absent from the target dictionary map to -2, which equals no
    valid code (and no null code, -1) — equality against translated codes is
    therefore exact. Shared by cross-dictionary comparisons and string-key
    joins.
    """
    src = col.dictionary
    if len(src) == 0:
        return jnp.full(col.data.shape, -2, jnp.int32)
    if len(target_dictionary) == 0:
        return jnp.full(col.data.shape, -2, jnp.int32)
    pos = np.searchsorted(target_dictionary, src)
    pos_c = np.clip(pos, 0, len(target_dictionary) - 1)
    present = (pos < len(target_dictionary)) & (target_dictionary[pos_c] == src)
    mapping = np.where(present, pos_c, -2).astype(np.int32)
    mapping_dev = jnp.asarray(mapping)
    return jnp.where(col.data >= 0,
                     jnp.take(mapping_dev, jnp.maximum(col.data, 0)), -2)


def literal_to_device(value, dtype: str, dictionary: Optional[np.ndarray]):
    """Encode a python literal for comparison against a device column.

    For STRING columns returns ``(lo, hi)`` searchsorted bounds into the
    dictionary: lo == searchsorted(dict, v, 'left'), hi == 'right' — every
    comparison op can be phrased over codes with these two ints (see
    ops/kernels.py:compare_literal).
    """
    if dtype == STRING:
        if dictionary is None:
            raise HyperspaceException("string literal against non-string column")
        v = str(value)
        lo = int(np.searchsorted(dictionary, v, side="left"))
        hi = int(np.searchsorted(dictionary, v, side="right"))
        return lo, hi
    if dtype == DATE:
        if isinstance(value, str):
            value = datetime.date.fromisoformat(value)
        if isinstance(value, datetime.date):
            return int((value - datetime.date(1970, 1, 1)).days)
        return int(value)
    if dtype == BOOL:
        return bool(value)
    if dtype in (FLOAT32, FLOAT64):
        return float(value)
    if isinstance(value, float) and not value.is_integer():
        # Fractional literal against an int column: int() truncation would
        # change comparison semantics (5 < 5.5 but not 5 < int(5.5));
        # jnp promotes the int column for the comparison instead.
        return value
    return int(value)
