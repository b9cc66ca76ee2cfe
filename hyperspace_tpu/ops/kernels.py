"""Core XLA compute kernels the engine is built from.

Everything here is jit-friendly (static shapes, no Python control flow on
traced values) except where a host sync is architecturally required (dynamic
result sizes: join output length, group count) — those sync points are single
scalars and are marked HOST SYNC.

These are the TPU-native equivalents of the distributed primitives catalogued
in SURVEY §2: hash-repartition (bucket_ids), sort-within-bucket
(lex_sort_indices), shuffle-free merge join (merge_join_indices over
co-partitioned buckets), and the lineage anti-filter (isin_sorted).

Shape-class execution (execution/shapes.py): the dynamic-size kernels accept
class-padded inputs with an explicit ``valid_count`` and can return padded
outputs (``padded_out=True``) so the executor keeps arrays on length classes
across operator boundaries instead of recompiling per exact length. The
padding contract each kernel honors internally:

- sorts prepend an is-pad key, so pad rows sort last and the valid prefix
  is byte-identical to the unpadded sort;
- searchsorted sentinels overwrite the pad tail with the dtype maximum and
  clamp the resulting bounds to the valid count;
- segment scatters route pad rows to an out-of-range segment id (XLA drops
  out-of-bounds scatter updates);
- expansion sizes (join match totals, group counts) are padded to their own
  length class before becoming static shape parameters.

Inputs that are tracers (the SPMD path calls these inside its own fused jit
programs, where shapes are already static) bypass padding entirely.
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..exceptions import HyperspaceException
from ..execution import shapes
from ..schema import BOOL, DATE, FLOAT32, FLOAT64, INT32, INT64, STRING

_M32 = np.uint32(0xFFFFFFFF)  # numpy scalar: no device alloc at import time


def _aot_kernel(label: str, jitted):
    """Route a module-level jitted utility kernel through the artifact
    store's AOT seam (artifacts/manager.py AotKernel): sessions that
    enable ``hyperspace.tpu.artifacts.enabled`` import/export these
    executables through the lake like banked stages, so a cold boot's
    op-by-op compile tail (gather, mask count, slice...) preloads too.
    Off sessions pay one manager probe and run the jitted original.
    CONVENTION at every wrapped call site: positional arguments are
    dynamic, keyword arguments are static."""
    try:
        from ..artifacts.manager import wrap_kernel
        return wrap_kernel(label, jitted)
    except Exception:
        return jitted


def _dtype_max(dtype):
    """Largest finite-orderable value of ``dtype`` (searchsorted sentinel:
    pads must not sort below any real key; ties are neutralized by
    clamping the searchsorted bounds to the valid count)."""
    if dtype == jnp.bool_:
        return True
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    return jnp.iinfo(dtype).max


# ---------------------------------------------------------------------------
# Fused jitted stage programs. Eager dispatch compiles each primitive
# separately — one tiny XLA program per (op, shape); a dynamic-size stage
# touching a fresh length class used to cost its whole op-chain in
# compiles. Each stage below is ONE compiled program per input signature
# instead. Python-int scalars (valid counts) become weak-typed scalar
# ARGUMENTS, so one program serves every count at a class.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("ascending", "masked"))
def _sort_perm(operands: Tuple[jax.Array, ...], n,
               ascending: Tuple[bool, ...], masked: bool) -> jax.Array:
    phys = operands[0].shape[0]
    iota = jnp.arange(phys, dtype=jnp.int32)
    ops = [_sort_key_view(k, a) for k, a in zip(operands, ascending)]
    num_keys = len(ops)
    if masked:
        ops = [iota >= jnp.int32(n)] + ops  # pads sort last
        num_keys += 1
    out = jax.lax.sort(ops + [iota], num_keys=num_keys, is_stable=True)
    return out[-1]


_sort_perm = _aot_kernel("sort_perm", _sort_perm)


@jax.jit
def _merge_bounds(right_keys_sorted: jax.Array, left_keys: jax.Array,
                  n_l, n_r) -> Tuple[jax.Array, jax.Array, jax.Array]:
    phys_r = right_keys_sorted.shape[0]
    iota_r = jnp.arange(phys_r, dtype=jnp.int32)
    rk = jnp.where(iota_r < jnp.int32(n_r), right_keys_sorted,
                   jnp.asarray(_dtype_max(right_keys_sorted.dtype),
                               right_keys_sorted.dtype))
    lo = jnp.minimum(jnp.searchsorted(rk, left_keys, side="left"), n_r)
    hi = jnp.minimum(jnp.searchsorted(rk, left_keys, side="right"), n_r)
    counts = (hi - lo).astype(jnp.int32)
    phys_l = left_keys.shape[0]
    counts = jnp.where(jnp.arange(phys_l, dtype=jnp.int32) < jnp.int32(n_l),
                       counts, 0)
    return lo, counts, jnp.sum(counts)


@partial(jax.jit, static_argnames=("masked",))
def _group_ids_from_keys(keys: Tuple[jax.Array, ...], n, masked: bool
                         ) -> Tuple[jax.Array, jax.Array]:
    """Fused change-mask + running ids. Returns (gids, last valid id);
    with ``masked``, pad rows are parked at the out-of-range id ``phys``
    (>= any group count) so segment scatters drop them."""
    phys = keys[0].shape[0]
    change = jnp.zeros(phys, dtype=jnp.bool_)
    for k in keys:
        change = change | jnp.concatenate(
            [jnp.zeros(1, jnp.bool_), k[1:] != k[:-1]])
    if masked:
        iota = jnp.arange(phys, dtype=jnp.int32)
        valid = iota < jnp.int32(n)
        change = change & valid
        gids = jnp.cumsum(change.astype(jnp.int32))
        last = jnp.max(gids)  # pads keep the running id constant past n-1
        return jnp.where(valid, gids, jnp.int32(phys)), last
    gids = jnp.cumsum(change.astype(jnp.int32))
    return gids, gids[-1] if phys else jnp.int32(0)


@partial(jax.jit, static_argnames=("num_segments", "op"))
def _segment(data, gids, num_segments: int, op: str):
    fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
          "max": jax.ops.segment_max}[op]
    return fn(data, gids, num_segments=num_segments)


@partial(jax.jit, static_argnames=("num_segments", "op", "widen"))
def _segment_agg(data, validity, gids, num_segments: int, op: str,
                 widen: bool):
    """Fused per-group aggregate: accumulator widening / null-sentinel
    substitution / valid counting / mean division all inside ONE program
    (they used to be separate eager ops, one compile each per class).
    Returns (value, counts) — counts is the per-group valid count (None
    when the caller needs no validity and op is not a mean)."""
    counts = None
    if validity is not None or op == "mean":
        ones = jnp.ones(gids.shape[0], jnp.int64) if validity is None \
            else validity.astype(jnp.int64)
        counts = jax.ops.segment_sum(ones, gids, num_segments=num_segments)
    if op in ("sum", "mean"):
        acc = data.astype(jnp.float64) \
            if widen and jnp.issubdtype(data.dtype, jnp.floating) \
            else (data.astype(jnp.int64) if widen else data)
        if validity is not None:
            acc = jnp.where(validity, acc, jnp.zeros((), acc.dtype))
        sums = jax.ops.segment_sum(acc, gids, num_segments=num_segments)
        if op == "sum":
            return sums, counts
        return (sums.astype(jnp.float64) /
                jnp.maximum(counts, 1).astype(jnp.float64)), counts
    sentinel_max = op == "min"  # invalid rows push past every real value
    if validity is not None:
        if jnp.issubdtype(data.dtype, jnp.floating):
            sent = jnp.finfo(data.dtype).max if sentinel_max \
                else jnp.finfo(data.dtype).min
        else:
            sent = jnp.iinfo(data.dtype).max if sentinel_max \
                else jnp.iinfo(data.dtype).min
        data = jnp.where(validity, data, jnp.asarray(sent, data.dtype))
    fn = jax.ops.segment_min if op == "min" else jax.ops.segment_max
    return fn(data, gids, num_segments=num_segments), counts


@partial(jax.jit, static_argnames=("phys",))
def _global_gids(n, phys: int):
    """Segment ids for a global aggregate over a class-padded table: 0
    for valid rows, the (dropped) out-of-range id ``phys`` for pads."""
    iota = jnp.arange(phys, dtype=jnp.int32)
    return jnp.where(iota < jnp.int32(n), jnp.int32(0), jnp.int32(phys))


@partial(jax.jit, static_argnames=("num_segments",))
def _segment_heads(gids, arrays: Tuple[jax.Array, ...], num_segments: int):
    """Fused segment_first_index + gather: each segment's first row's
    values, for every array, in one program. Pad segments gather row 0
    via clip (never read as data)."""
    firsts = jax.ops.segment_min(
        jnp.arange(gids.shape[0], dtype=jnp.int32), gids,
        num_segments=num_segments)
    return tuple(jnp.take(a, firsts, axis=0, mode="clip") for a in arrays)


@partial(jax.jit, static_argnames=("num_segments", "op"))
def _gather_segment(partial_vals, order, gids, num_segments: int, op: str):
    """Fused gather + segment reduce (the two-phase combine step)."""
    fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
          "max": jax.ops.segment_max}[op]
    return fn(jnp.take(partial_vals, order, axis=0, mode="clip"), gids,
              num_segments=num_segments)


@partial(jax.jit, static_argnames=("num_segments",))
def _segment_ones(gids, num_segments: int):
    return jax.ops.segment_sum(jnp.ones(gids.shape[0], jnp.int64), gids,
                               num_segments=num_segments)


@partial(jax.jit, static_argnames=("num_segments",))
def _segment_iota_min(gids, num_segments: int):
    return jax.ops.segment_min(
        jnp.arange(gids.shape[0], dtype=jnp.int32), gids,
        num_segments=num_segments)


# ---------------------------------------------------------------------------
# Hashing (bucket assignment). murmur3-finalizer avalanche over uint32 lanes.
# ---------------------------------------------------------------------------

def _fmix32(x: jax.Array) -> jax.Array:
    x = x & _M32
    x = x ^ (x >> 16)
    x = (x * np.uint32(0x85EBCA6B)) & _M32
    x = x ^ (x >> 13)
    x = (x * np.uint32(0xC2B2AE35)) & _M32
    x = x ^ (x >> 16)
    return x


def fold_u32(data: jax.Array, dtype: str,
             dictionary: Optional[np.ndarray] = None) -> jax.Array:
    """Value-stable fold of a column into pre-avalanche uint32 words.

    This is the 64→32-bit (and string→crc) part of hash32_values, split out
    so the Pallas fused hash+bucket kernel (pallas_kernels.fused_hash_bucket)
    can consume the same fold and produce bit-identical hashes: the kernel
    applies the murmur finalizer to exactly these words.
    """
    if dtype == STRING:
        if dictionary is None:
            raise HyperspaceException("hash32 of string column requires dictionary")
        host_hashes = np.array(
            [zlib.crc32(s.encode("utf-8")) for s in dictionary], dtype=np.uint32) \
            if len(dictionary) else np.zeros(1, np.uint32)
        table = jnp.asarray(host_hashes)
        codes = jnp.clip(data, 0, max(len(dictionary) - 1, 0))
        return jnp.take(table, codes)
    if dtype in (INT32, DATE, BOOL):
        return data.astype(jnp.uint32)
    if dtype == INT64:
        u = data.astype(jnp.uint64)
        lo = (u & np.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (u >> np.uint64(32)).astype(jnp.uint32)
        return lo ^ (hi * np.uint32(0x9E3779B9))
    if dtype == FLOAT32:
        return jax.lax.bitcast_convert_type(data, jnp.uint32)
    if dtype == FLOAT64:
        bits = jax.lax.bitcast_convert_type(data, jnp.uint64)
        lo = (bits & np.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (bits >> np.uint64(32)).astype(jnp.uint32)
        return lo ^ (hi * np.uint32(0x9E3779B9))
    raise HyperspaceException(f"Cannot hash dtype {dtype}")


def hash32_values(data: jax.Array, dtype: str,
                  dictionary: Optional[np.ndarray] = None) -> jax.Array:
    """Stable 32-bit hash of a column's *values* (not its encoding).

    For strings the hash is computed from the dictionary entries' bytes on
    host (crc32) and gathered by code on device — so two tables with
    different dictionaries hash equal strings equally, which is what makes
    bucket co-partitioning work across index/source/appended data.
    """
    if shapes._is_tracer(data):
        return _fmix32(fold_u32(data, dtype, dictionary))
    if dtype == STRING:
        if dictionary is None:
            raise HyperspaceException("hash32 of string column requires dictionary")
        host_hashes = np.array(
            [zlib.crc32(s.encode("utf-8")) for s in dictionary], dtype=np.uint32) \
            if len(dictionary) else np.zeros(1, np.uint32)
        return _hash32_string(data, jnp.asarray(host_hashes))
    return _hash32_prim(data, dtype)


@jax.jit
def _hash32_string(codes: jax.Array, table: jax.Array) -> jax.Array:
    safe = jnp.clip(codes, 0, table.shape[0] - 1)
    return _fmix32(jnp.take(table, safe))


@partial(jax.jit, static_argnames=("dtype",))
def _hash32_prim(data: jax.Array, dtype: str) -> jax.Array:
    return _fmix32(fold_u32(data, dtype, None))


def _fmix32_host(x: int) -> int:
    """Host mirror of _fmix32 for single literals (bucket pruning)."""
    x &= 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & 0xFFFFFFFF
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & 0xFFFFFFFF
    x ^= x >> 16
    return x


def hash_combine_host(h1: int, h2: int) -> int:
    """Host mirror of hash_combine."""
    return (h1 ^ ((h2 + 0x9E3779B9 + ((h1 << 6) & 0xFFFFFFFF) + (h1 >> 2))
                  & 0xFFFFFFFF)) & 0xFFFFFFFF


def hash32_value_host(value, dtype: str) -> int:
    """Host-side hash of one literal, identical to hash32_values on device.
    Used to compute the bucket a literal lands in (bucket pruning)."""
    import struct

    if dtype == STRING:
        return _fmix32_host(zlib.crc32(str(value).encode("utf-8")))
    if dtype in (INT32, DATE, BOOL):
        return _fmix32_host(int(value) & 0xFFFFFFFF)
    if dtype == INT64:
        u = int(value) & 0xFFFFFFFFFFFFFFFF
        lo, hi = u & 0xFFFFFFFF, u >> 32
        return _fmix32_host((lo ^ ((hi * 0x9E3779B9) & 0xFFFFFFFF)) & 0xFFFFFFFF)
    if dtype == FLOAT32:
        bits = struct.unpack("<I", struct.pack("<f", float(value)))[0]
        return _fmix32_host(bits)
    if dtype == FLOAT64:
        bits = struct.unpack("<Q", struct.pack("<d", float(value)))[0]
        lo, hi = bits & 0xFFFFFFFF, bits >> 32
        return _fmix32_host((lo ^ ((hi * 0x9E3779B9) & 0xFFFFFFFF)) & 0xFFFFFFFF)
    raise HyperspaceException(f"Cannot hash dtype {dtype}")


def hash_combine(h1: jax.Array, h2: jax.Array) -> jax.Array:
    """Boost-style combiner over uint32."""
    return (h1 ^ ((h2 + np.uint32(0x9E3779B9) + (h1 << 6) + (h1 >> 2)) & _M32)) & _M32


def bucket_ids(hashes: jax.Array, num_buckets: int) -> jax.Array:
    return (hashes % np.uint32(num_buckets)).astype(jnp.int32)


@jax.jit
def _masked_count(mask: jax.Array, n) -> Tuple[jax.Array, jax.Array]:
    """(mask with pad tail cleared, survivor count) in one program."""
    valid = jnp.arange(mask.shape[0], dtype=jnp.int32) < jnp.int32(n)
    mask = mask & valid
    return mask, jnp.sum(mask)


_masked_count = _aot_kernel("masked_count", _masked_count)


@partial(jax.jit, static_argnames=("size",))
def _nonzero_pad(mask: jax.Array, size: int) -> jax.Array:
    # jnp.flatnonzero, in int32: under x64 its cumsum and bincount run in
    # emulated int64 on the TPU. Compiled for a described v5e at 6M rows,
    # that took 24.6 s and this form 5.3 s (PR 21).
    pos = jnp.where(mask, jnp.cumsum(mask, dtype=jnp.int32) - 1, size)
    return jnp.zeros(size, jnp.int32).at[pos].set(
        jnp.arange(mask.shape[0], dtype=jnp.int32), mode="drop")


_nonzero_pad = _aot_kernel("nonzero_pad", _nonzero_pad)


def mask_count_nonzero(mask, valid_rows: Optional[int], padded: bool):
    """Fused filter front-end: clear the pad tail, count survivors (one
    scalar HOST SYNC), and emit class-padded gather indices (filler 0).
    Two compiled programs per mask class instead of the ~6 eager ops of
    flatnonzero + masking."""
    from ..execution.shapes import padded_length
    if valid_rows is not None:
        mask, cnt = _masked_count(mask, valid_rows)
        m = int(cnt)  # HOST SYNC (single scalar)
    else:
        m = int(jnp.sum(mask))  # HOST SYNC (single scalar)
    size = padded_length(m) if padded else m
    return _nonzero_pad(mask, size=size), m


@partial(jax.jit, static_argnames=("length", "unique"))
def position_table(keys: jax.Array, length: int, unique: bool):
    """Direct-address position table of ascending integer ``keys``:
    ``slot[k - keys[0]]`` holds the row of key ``k`` (one of them where
    keys repeat, ``unique`` False), -1 where no key falls. Returns
    (slot, keys[:1]): the base key rides beside it as a 1-element array,
    so the probing program takes it as data, not as a constant."""
    kmin = keys[:1]
    rows = jnp.arange(keys.shape[0], dtype=jnp.int32)
    slot = jnp.full(length, -1, jnp.int32).at[keys - kmin[0]].set(
        rows, indices_are_sorted=True, unique_indices=unique)
    return slot, kmin


@partial(jax.jit, static_argnames=("dtype", "num_buckets", "check"))
def _composite_bucket_key(keys: jax.Array, n, dtype: str,
                          num_buckets: int, check: bool):
    """Fused hash -> bucket -> (bucket << 32 | biased key) composite for
    the shuffle-free merge-join probe (one program per class instead of
    the ~8-op eager chain). With ``check``, also returns max(|key[:n]|)
    so the caller's int32-fit test costs no extra program."""
    h = _fmix32(fold_u32(keys, dtype, None))
    b = (h % np.uint32(num_buckets)).astype(jnp.int32)
    comp = pack2_int32(b, keys.astype(jnp.int32))
    if not check:
        return comp, jnp.zeros((), keys.dtype)
    valid = jnp.arange(keys.shape[0], dtype=jnp.int32) < jnp.int32(n)
    extreme = jnp.max(jnp.where(valid, jnp.abs(keys),
                                jnp.zeros((), keys.dtype)))
    return comp, extreme


def bucket_composite_keys(keys: jax.Array, dtype: str, num_buckets: int,
                          valid_count: Optional[int] = None):
    """(composite probe keys, max |key| over the valid prefix — 0 when the
    dtype needs no int32-fit check)."""
    if shapes._is_tracer(keys):
        h = hash32_values(keys, dtype)
        comp = pack2_int32(bucket_ids(h, num_buckets),
                           keys.astype(jnp.int32))
        return comp, jnp.zeros((), keys.dtype)
    n = int(keys.shape[0]) if valid_count is None else int(valid_count)
    check = keys.dtype == jnp.int64 and keys.shape[0] > 0
    return _composite_bucket_key(keys, n, dtype, num_buckets, check)


# Fused predicate programs: one compiled program per predicate STRUCTURE
# (expression shape + column dtypes/validity + literal type tags — see
# evaluator.eval_predicate_mask_counted). Literal VALUES arrive as runtime
# scalar arguments, so a serving workload sweeping literals reuses one
# program. The builder also folds in the pad-tail mask and the survivor
# count, replacing the per-op compare/kleene/mask/count chain with a
# single program per (structure, class). The wrappers live in the
# process-wide PROGRAM BANK (serving/program_bank.py — the serving
# tier's explicit, size-bounded, instrumented registry; one session's
# warm-up pays every session's compiles); the jax.jit call stays HERE,
# in the lint-sanctioned instrumented module.


def _col_shape_vector(col_arrays) -> tuple:
    """Shape-class vector of a fused stage's inputs (the bank's hit/miss
    accounting unit; jax re-keys executables under the wrapper)."""
    return tuple(int(d.shape[0]) for d, _v in col_arrays)


def run_fused_predicate(key, builder, col_arrays, lit_args, n):
    """Run (compiling once per structure key x input signature) the fused
    predicate ``builder(col_arrays, lit_args, n) -> (mask, count)``.
    ``builder`` must be a pure function fully determined by ``key``.
    The bank is a bounded LRU over stages: overflowing evicts the single
    coldest structure (dropping its jit wrapper and compiled
    executables), never the whole map — a clear() would re-trace every
    hot predicate at once, the recompilation storm this layer exists to
    prevent."""
    from ..serving.program_bank import get_bank
    jitted = get_bank().lookup(("fused-predicate", key),
                               _col_shape_vector(col_arrays),
                               lambda: jax.jit(builder))
    return jitted(col_arrays, lit_args, n)


def run_fused_predicate_sweep(key, builder, col_arrays, lit_matrix, n,
                              batch: int):
    """Cross-query literal sweep: ONE invocation evaluating ``batch``
    literal vectors against the same columns — ``builder`` vmapped over
    the stacked literal axis. Returns (masks[batch, rows],
    counts[batch]). The stage key extends the single-query key with the
    batch class, so sweeps and singles never collide in the bank."""
    from ..serving.program_bank import get_bank
    jitted = get_bank().lookup(
        ("fused-predicate-sweep", key, batch),
        _col_shape_vector(col_arrays) + (batch,),
        lambda: jax.jit(jax.vmap(builder, in_axes=(None, 0, None))))
    return jitted(col_arrays, lit_matrix, n)


def run_fused_region(key, shape_vec, factory, args):
    """Run a whole-plan fused REGION program (execution/fusion.py): one
    jitted program per (region fingerprint, shape-class vector) in the
    process-wide ProgramBank. ``factory()`` must return a pure builder
    fully determined by ``key`` (the bank contract); the jax.jit call
    stays HERE, in the lint-sanctioned instrumented module, so the r07
    compile counter attributes every region compile."""
    from ..serving.program_bank import get_bank
    jitted = get_bank().lookup(("fused-region", key), tuple(shape_vec),
                               lambda: jax.jit(factory()))
    return jitted(args)


def nonzero_pad_indices(mask, size: int):
    """Class-padded indices of a mask's True entries (filler 0)."""
    return _nonzero_pad(mask, size=size)


@partial(jax.jit, static_argnames=("is_and",))
def _kleene_jit(ld, lv, rd, rv, is_and: bool):
    """Fused Kleene 3-valued AND/OR (TRUE OR NULL = TRUE, FALSE AND NULL
    = FALSE). ``lv``/``rv`` may be None (all-valid side). Returns
    (true, known)."""
    n = ld.shape[0]
    lvv = lv if lv is not None else jnp.ones(n, jnp.bool_)
    rvv = rv if rv is not None else jnp.ones(n, jnp.bool_)
    lt, lf = lvv & ld, lvv & ~ld
    rt, rf = rvv & rd, rvv & ~rd
    if is_and:
        true, false = lt & rt, lf | rf
    else:
        true, false = lt | rt, lf & rf
    return true, true | false


_kleene_jit = _aot_kernel("kleene", _kleene_jit)


def kleene_and_or(ld, lv, rd, rv, is_and: bool):
    if shapes._is_tracer(ld):  # SPMD evaluates expressions inside its jit
        n = ld.shape[0]
        lvv = lv if lv is not None else jnp.ones(n, jnp.bool_)
        rvv = rv if rv is not None else jnp.ones(n, jnp.bool_)
        lt, lf = lvv & ld, lvv & ~ld
        rt, rf = rvv & rd, rvv & ~rd
        true, false = (lt & rt, lf | rf) if is_and else (lt | rt, lf & rf)
        return true, true | false
    return _kleene_jit(ld, lv, rd, rv, is_and=is_and)


def gather_arrays(indices, arrays):
    """Fused multi-array row gather: one compiled program per signature
    instead of one take per column. Out-of-range indices (pad tails of
    class-padded index arrays) clip — clipped rows land in the pad region
    of the result and are never read as data."""
    arrays = tuple(arrays)
    if shapes._is_tracer(indices) or any(shapes._is_tracer(a)
                                         for a in arrays):
        return tuple(jnp.take(a, indices, axis=0, mode="clip")
                     for a in arrays)
    return _gather_jit(indices, arrays)


@jax.jit
def _gather_jit(indices, arrays: Tuple[jax.Array, ...]):
    return tuple(jnp.take(a, indices, axis=0, mode="clip") for a in arrays)


_gather_jit = _aot_kernel("gather", _gather_jit)


@partial(jax.jit, static_argnames=("start", "stop"))
def _slice_jit(arrays: Tuple[jax.Array, ...], start: int, stop: int):
    return tuple(a[start:stop] for a in arrays)


_slice_jit = _aot_kernel("slice", _slice_jit)


@partial(jax.jit, static_argnames=("target",))
def _pad_jit(arr, fill, target: int):
    return jax.lax.pad(arr, jnp.asarray(fill, arr.dtype),
                       [(0, target - arr.shape[0], 0)])


_pad_jit = _aot_kernel("pad", _pad_jit)


def pad_array(arr, fill, target: int):
    """shapes.pad_to device back-end: ONE program per (class, dtype,
    fill signature) — the eager spelling paid a convert + a pad program
    and neither survived a process restart."""
    return _pad_jit(arr, fill, target=target)


@jax.jit
def _adjacent_dup_jit(codes: jax.Array) -> jax.Array:
    return jnp.any(codes[1:] == codes[:-1])


_adjacent_dup_jit = _aot_kernel("adjacent_dup", _adjacent_dup_jit)


def has_adjacent_duplicates(codes) -> jax.Array:
    """True iff a SORTED key vector has equal neighbors (the fused-join
    m:n probe-side check): two slices + eq + any fused in one program."""
    return _adjacent_dup_jit(codes)


@partial(jax.jit, static_argnames=("dtype",))
def _cast_jit(arr, dtype: str):
    return arr.astype(jnp.dtype(dtype))


_cast_jit = _aot_kernel("cast", _cast_jit)


def cast_array(arr, dtype):
    """Dtype cast as one banked program (callers should skip the call
    entirely when the dtype already matches)."""
    return _cast_jit(arr, dtype=jnp.dtype(dtype).name)


def slice_arrays(arrays, start: int, stop: int):
    """Fused multi-array row slice: one compiled program per (signature,
    start, stop) instead of one slice per column buffer (Table.slice /
    Table.compact). NOTE the bounds are static — a data-dependent stop
    still compiles per distinct value, which is why final results trim at
    the host boundary instead (executor.execute) and only interior
    compaction boundaries (outer joins, windows, SPMD leaves) pay this."""
    arrays = tuple(arrays)
    if any(shapes._is_tracer(a) for a in arrays):
        return tuple(a[start:stop] for a in arrays)
    return _slice_jit(arrays, start=start, stop=stop)


# ---------------------------------------------------------------------------
# Sorting.
# ---------------------------------------------------------------------------

def _sort_key_view(data: jax.Array, ascending: bool) -> jax.Array:
    """Transform a key column so ascending lax.sort realizes the requested
    direction (numeric negate; safe for codes/int/float w/o NaN)."""
    if ascending:
        return data
    if data.dtype == jnp.bool_:
        return ~data
    return -data


def lex_sort_indices(keys: Sequence[jax.Array],
                     ascending: Optional[Sequence[bool]] = None,
                     valid_count: Optional[int] = None,
                     padded_out: bool = False,
                     pad: bool = True) -> jax.Array:
    """Indices that stably sort by keys[0], then keys[1], ... (lexicographic).

    lax.sort sorts by the leading operands; we append iota as the payload.

    Shape classes: inputs longer than ``valid_count`` (or padded here to
    their length class) get a leading is-pad sort key, so pad rows land
    after every real row and the valid prefix of the permutation is
    byte-identical to the unpadded sort. ``padded_out`` keeps the padded
    permutation (pad entries index pad rows) for padded gathers.
    """
    if ascending is None:
        ascending = [True] * len(keys)
    phys = int(keys[0].shape[0])
    n = phys if valid_count is None else int(valid_count)
    if shapes._is_tracer(keys[0]) or phys == 0:
        iota = jnp.arange(phys, dtype=jnp.int32)
        operands = [_sort_key_view(k, a)
                    for k, a in zip(keys, ascending)] + [iota]
        out = jax.lax.sort(operands, num_keys=len(keys), is_stable=True)
        return out[-1]
    if valid_count is None and pad:
        # ``pad=False`` opts out for whole-dataset work at a stable
        # per-dataset length (the index build): padding there buys no
        # compile reuse and costs real sort work on the tail.
        cls = shapes.padded_length(phys)
        if cls != phys:
            keys = [shapes.pad_to(k, cls) for k in keys]
            phys = cls
    padded = phys != n
    perm = _sort_perm(tuple(keys), n, ascending=tuple(ascending),
                      masked=padded)
    if padded and not padded_out:
        return shapes.unpad(perm, n)
    return perm


# ---------------------------------------------------------------------------
# Merge join over sorted keys.
# ---------------------------------------------------------------------------

def merge_join_indices(left_keys: jax.Array, right_keys_sorted: jax.Array,
                       return_counts: bool = False,
                       left_valid: Optional[int] = None,
                       right_valid: Optional[int] = None,
                       padded_out: bool = False):
    """Inner equi-join: for each left row, all matching right rows.

    ``right_keys_sorted`` must be ascending over its valid prefix. Returns
    (left_idx, right_idx) gather indices — plus the per-left-row match
    counts when ``return_counts`` (outer joins pad count-0 rows). Output
    length is data-dependent → one scalar HOST SYNC.

    Shape classes: padded inputs declare their valid prefix via
    ``left_valid``/``right_valid`` (exact inputs are padded here). The pad
    tail of the right side is overwritten with the dtype maximum to keep
    the searchsorted precondition, the bounds are clamped to the valid
    count (which also neutralizes real keys tying with the sentinel), and
    pad left rows contribute zero matches. The expansion size is padded to
    its own length class so one compiled expansion program serves every
    total in the class. With ``padded_out`` the padded (left_idx,
    right_idx, total) triple is returned for padded gathers (the tail of
    a padded expansion repeats in-bounds indices).
    """
    if shapes._is_tracer(left_keys):
        # The expansion length is data-dependent: under tracing the
        # host sync it requires is impossible (int() of a tracer is a
        # ConcretizationTypeError — the HS311 bug class). Trace-side
        # join programs precompute static capacities instead
        # (parallel/sharding.py); fail typed rather than deep inside
        # jax internals.
        raise HyperspaceException(
            "merge_join_indices cannot run under tracing: the join "
            "expansion length is data-dependent and would need a "
            "device->host sync. Traced callers must precompute a "
            "static match capacity (see parallel/sharding.py).")
    n_l = int(left_keys.shape[0]) if left_valid is None else int(left_valid)
    n_r = int(right_keys_sorted.shape[0]) if right_valid is None \
        else int(right_valid)
    if left_valid is None:
        left_keys = shapes.pad_to(
            left_keys, shapes.padded_length(n_l))
    if right_valid is None:
        right_keys_sorted = shapes.pad_to(
            right_keys_sorted, shapes.padded_length(n_r))
    if left_keys.dtype != right_keys_sorted.dtype:
        # One comparable dtype before the fused program (mixed-width int
        # keys reach here via executor joins).
        wide = jnp.promote_types(left_keys.dtype, right_keys_sorted.dtype)
        left_keys = left_keys.astype(wide)
        right_keys_sorted = right_keys_sorted.astype(wide)
    lo, counts, total_dev = _merge_bounds(right_keys_sorted, left_keys,
                                          n_l, n_r)
    total = int(total_dev)  # HOST SYNC (single scalar).
    cls_t = shapes.padded_length(total)
    li, ri = _expand_matches(counts, lo, cls_t)
    if padded_out:
        if return_counts:
            return li, ri, total, counts
        return li, ri, total
    li, ri = shapes.unpad(li, total), shapes.unpad(ri, total)
    if return_counts:
        return li, ri, shapes.unpad(counts, n_l)
    return li, ri


@partial(jax.jit, static_argnames=("total",))
def _expand_matches(counts: jax.Array, lo: jax.Array, total: int
                    ) -> Tuple[jax.Array, jax.Array]:
    n_left = counts.shape[0]
    left_idx = jnp.repeat(jnp.arange(n_left, dtype=jnp.int32), counts,
                          total_repeat_length=total)
    starts = jnp.cumsum(counts) - counts
    base = jnp.repeat(starts.astype(jnp.int32), counts, total_repeat_length=total)
    within = jnp.arange(total, dtype=jnp.int32) - base
    right_idx = jnp.repeat(lo.astype(jnp.int32), counts,
                           total_repeat_length=total) + within
    # NOTE on padded totals: jnp.repeat pads its output by repeating
    # trailing values, so the tail of a padded expansion can hold
    # out-of-range right indices — consumers gather with clip mode and
    # slice to the true total before anything order-sensitive.
    return left_idx, right_idx


def change_mask(sorted_keys: Sequence[jax.Array]) -> jax.Array:
    """True where a row's key tuple differs from the previous row's (rows
    already sorted by the keys); row 0 is False."""
    n = int(sorted_keys[0].shape[0])
    change = jnp.zeros(n, dtype=jnp.bool_)
    for k in sorted_keys:
        change = change | jnp.concatenate(
            [jnp.zeros(1, jnp.bool_), k[1:] != k[:-1]])
    return change


def dense_rank(keys: Sequence[jax.Array]) -> jax.Array:
    """Dense rank of each row's key *tuple* in lexicographic order.

    Equal tuples get equal ranks and ranks are order-preserving, so a
    multi-column equi-join reduces to a single int32-key join on the ranks
    of the two sides' concatenated key columns. Fully on device — no host
    sync (the consumer never needs the rank count).
    """
    n = int(keys[0].shape[0])
    if n == 0:
        return jnp.zeros(0, jnp.int32)
    order = lex_sort_indices(keys)
    change = change_mask([jnp.take(k, order) for k in keys])
    gids = jnp.cumsum(change.astype(jnp.int32))
    return jnp.zeros(n, jnp.int32).at[order].set(gids)


def pack2_int32(a: jax.Array, b: jax.Array) -> jax.Array:
    """Pack two int32 key columns into one int64 composite key.

    ``b`` is sign-biased (XOR 0x80000000) so the packed composite orders the
    same as (a asc, b signed-asc) — without the bias, negative ``b`` values
    sort above positive ones in the low 32 bits and break the merge join's
    sortedness precondition.
    """
    b_biased = (b.astype(jnp.int64) ^ np.int64(0x80000000)) & np.int64(0xFFFFFFFF)
    return (a.astype(jnp.int64) << np.int64(32)) | b_biased


# ---------------------------------------------------------------------------
# Grouping / segmented aggregation (over sorted group keys).
# ---------------------------------------------------------------------------

def group_ids_from_sorted(keys: Sequence[jax.Array],
                          valid_count: Optional[int] = None,
                          padded_out: bool = False) -> Tuple[jax.Array, int]:
    """Segment ids for rows already sorted by ``keys``.

    Returns (group_id per row, number of groups). One scalar HOST SYNC.

    Shape classes: with ``padded_out`` the ids stay at the padded input
    length, pad rows carrying an out-of-range id (the array's physical
    length — always >= the group count), so segment scatters drop them.
    """
    phys = int(keys[0].shape[0])
    n = phys if valid_count is None else int(valid_count)
    if n == 0:
        return jnp.zeros(phys if padded_out else 0, jnp.int32), 0
    padded = phys != n
    gids, last = _group_ids_from_keys(tuple(keys), n, masked=padded)
    num_groups = int(last) + 1  # HOST SYNC (single scalar).
    if padded and not padded_out:
        return shapes.unpad(gids, n), num_groups
    return gids, num_groups


def _segment_cap(num_groups: int, gids) -> int:
    """Static segment count for the scatter: the group count's length
    class (out-of-range pad ids land in dropped/sliced segments)."""
    if shapes._is_tracer(gids):
        return num_groups
    return max(shapes.padded_length(num_groups), num_groups)


def segment_sum(data: jax.Array, gids: jax.Array, num_groups: int,
                padded_out: bool = False) -> jax.Array:
    if shapes._is_tracer(data) or shapes._is_tracer(gids):
        return jax.ops.segment_sum(data, gids, num_segments=num_groups)
    out = _segment(data, gids, _segment_cap(num_groups, gids), "sum")
    return out if padded_out else shapes.unpad(out, num_groups)


def segment_count(gids: jax.Array, num_groups: int,
                  validity: Optional[jax.Array] = None,
                  padded_out: bool = False) -> jax.Array:
    if validity is None:
        if shapes._is_tracer(gids):
            return jax.ops.segment_sum(jnp.ones(gids.shape[0], jnp.int64),
                                       gids, num_segments=num_groups)
        out = _segment_ones(gids, _segment_cap(num_groups, gids))
        return out if padded_out else shapes.unpad(out, num_groups)
    return segment_sum(validity.astype(jnp.int64), gids, num_groups,
                       padded_out=padded_out)


def segment_min(data: jax.Array, gids: jax.Array, num_groups: int,
                padded_out: bool = False) -> jax.Array:
    if shapes._is_tracer(data) or shapes._is_tracer(gids):
        return jax.ops.segment_min(data, gids, num_segments=num_groups)
    out = _segment(data, gids, _segment_cap(num_groups, gids), "min")
    return out if padded_out else shapes.unpad(out, num_groups)


def segment_max(data: jax.Array, gids: jax.Array, num_groups: int,
                padded_out: bool = False) -> jax.Array:
    if shapes._is_tracer(data) or shapes._is_tracer(gids):
        return jax.ops.segment_max(data, gids, num_segments=num_groups)
    out = _segment(data, gids, _segment_cap(num_groups, gids), "max")
    return out if padded_out else shapes.unpad(out, num_groups)


def segment_agg(data: jax.Array, validity, gids: jax.Array,
                num_groups: int, op: str, widen: bool = True,
                padded_out: bool = False):
    """Fused null-aware per-group aggregate (see _segment_agg). Returns
    (value, per-group valid counts or None)."""
    cap = _segment_cap(num_groups, gids)
    value, counts = _segment_agg(data, validity, gids, cap, op, widen)
    if not padded_out:
        value = shapes.unpad(value, num_groups)
        if counts is not None:
            counts = shapes.unpad(counts, num_groups)
    return value, counts


def segment_heads(gids: jax.Array, arrays, num_groups: int,
                  padded_out: bool = False):
    """Each segment's first row's values for every array in ``arrays``
    (fused first-index + gather; rows sorted by group key)."""
    cap = _segment_cap(num_groups, gids)
    out = _segment_heads(gids, tuple(arrays), cap)
    if not padded_out:
        out = tuple(shapes.unpad(a, num_groups) for a in out)
    return out


def gather_segment(partial_vals: jax.Array, order: jax.Array,
                   gids: jax.Array, num_groups: int, op: str,
                   padded_out: bool = False) -> jax.Array:
    """Fused gather-through-permutation + segment reduce (two-phase
    aggregation's combine step)."""
    cap = _segment_cap(num_groups, gids)
    out = _gather_segment(partial_vals, order, gids, cap, op)
    return out if padded_out else shapes.unpad(out, num_groups)


def global_segment_ids(valid_count: int, phys: int) -> jax.Array:
    """Segment ids for a global aggregate over a class-padded table."""
    return _global_gids(valid_count, phys=phys)


def segment_first_index(gids: jax.Array, num_groups: int,
                        padded_out: bool = False) -> jax.Array:
    """Index of each group's first row (rows sorted by group key). In a
    padded output, segments past the group count hold the int32 maximum
    (segment_min identity) — gather through them with clip mode only."""
    if shapes._is_tracer(gids):
        return jax.ops.segment_min(
            jnp.arange(gids.shape[0], dtype=jnp.int32), gids,
            num_segments=num_groups)
    out = _segment_iota_min(gids, _segment_cap(num_groups, gids))
    return out if padded_out else shapes.unpad(out, num_groups)


# ---------------------------------------------------------------------------
# Membership (lineage anti-filter: Not(In(lineage, deletedIds))).
# ---------------------------------------------------------------------------

def isin_sorted(data: jax.Array, sorted_values: jax.Array) -> jax.Array:
    """Vectorized membership of ``data`` in ascending ``sorted_values``."""
    if sorted_values.shape[0] == 0:
        return jnp.zeros(data.shape[0], jnp.bool_)
    pos = jnp.searchsorted(sorted_values, data)
    pos = jnp.clip(pos, 0, sorted_values.shape[0] - 1)
    return jnp.take(sorted_values, pos) == data
