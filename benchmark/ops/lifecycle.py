"""Index lifecycle operations: create, delete and vacuum of one of the
configuration's covering indexes, through the public ``Hyperspace`` API,
as a data engineer rebuilds an index after a bulk load.

An op of a traffic file::

    {"kind": "lifecycle", "index": "<a covering index of the config>",
     "steps": ["create", "delete", "vacuum"]}

``create`` is the set-up's own build (``harness.build_indexes``, with the
index's bucket count and row-group size), ``delete`` is
``Hyperspace.delete_index`` and ``vacuum`` ``Hyperspace.vacuum_index``.
The harness times each step. Between the steps, outside their time:

- after ``create``, the index's files, as the log entry lists them, are
  read with pyarrow and digested (``bucket_reference.digest``: rows, a
  64-bit sum of row hashes, and sortedness, per bucket);
- after ``vacuum``, the files left under the index's version directories
  (``v__=*``) are counted, and the index must no longer be listed.

``check`` holds the window's cycles to the plain reference
(``bucket_reference``), computed once from the lake: the first cycle row
for row; a later cycle whose digest differs from the reference's row for
row too, so that each count is a count of rows.
"""

from __future__ import annotations

import contextlib
import glob
import os
from typing import Dict, List

import pyarrow.parquet as pq

import bucket_reference as R

STEPS = ("create", "delete", "vacuum")
CHECKS = ("rows_wrong", "rows_misplaced", "unsorted_buckets",
          "leftover_files")


def index_of(config: dict, op: dict) -> dict:
    """The configuration's entry of the index the op names."""
    ix = next((i for i in config["indexes"] if i["name"] == op["index"]),
              None)
    if ix is None or ix["kind"] != "covering":
        raise ValueError(f"op names {op['index']!r}, which is not a "
                         f"covering index of the configuration")
    unknown = [s for s in op["steps"] if s not in STEPS]
    if unknown:
        raise ValueError(f"unknown lifecycle steps {unknown}")
    return ix


def run(ctx, cycle) -> None:
    """One cycle of the op's steps."""
    ix = index_of(ctx.config, cycle.op)
    name, rec = ix["name"], cycle.record
    for step in cycle.op["steps"]:
        if step == "create":
            ctx.step(cycle, step, lambda: ctx.build([ix]))
            ctx.aside(cycle, "bench.digest", lambda: _digest(ctx, rec, ix))
        elif step == "delete":
            ctx.step(cycle, step, lambda: ctx.hs.delete_index(name))
        else:
            ctx.step(cycle, step, lambda: ctx.hs.vacuum_index(name))
            ctx.aside(cycle, "bench.leftover",
                      lambda: _after_vacuum(ctx, rec, name))


def _digest(ctx, rec: dict, ix: dict) -> None:
    files = ctx.hs.index_manager.get_index(ix["name"]).content.files
    cols = ix["indexed"] + ix["included"]
    parts = [(R.bucket_of_file(f), pq.read_table(f, columns=cols))
             for f in files]
    rec["source_rows"] = ctx.lake.rows[ix["table"]]
    rec["index_dir"] = os.path.dirname(os.path.dirname(files[0]))
    rec["bytes"] = sum(os.path.getsize(f) for f in files)
    rec["digest"] = R.digest(parts, ix["indexed"], cols)
    # The rows are kept for the check where it has to compare them: the
    # first cycle, and one whose digest differs from the first's.
    first = ctx.state.setdefault("first_digest", rec["digest"])
    if first is rec["digest"] or first != rec["digest"]:
        rec["parts"] = parts


def _after_vacuum(ctx, rec: dict, name: str) -> None:
    rec["leftover"] = sum(
        len(files) for v in glob.glob(os.path.join(rec["index_dir"], "v__=*"))
        for _, _, files in os.walk(v))
    if name in set(ctx.hs.indexes()["name"]):
        raise RuntimeError(f"index {name} is still listed after vacuum")


def check(lake, config: dict, cycles: List) -> Dict[str, int]:
    """The window's counts against the plain reference, summed over its
    cycles."""
    out = dict.fromkeys(CHECKS, 0)
    refs: Dict[str, tuple] = {}
    for c in cycles:
        rec = c.record
        out["leftover_files"] += rec.get("leftover", 0)
        if "digest" not in rec:
            continue
        ix = index_of(config, c.op)
        if ix["name"] not in refs:
            cols = ix["indexed"] + ix["included"]
            source = pq.read_table(lake.dirs[ix["table"]], columns=cols)
            buckets = R.bucket_ids(source, ix["indexed"], ix["num_buckets"])
            refs[ix["name"]] = (source, R.reference_digest(
                source, buckets, cols), [])
        source, want, compared = refs[ix["name"]]
        if "parts" in rec:
            got = R.compare(rec["parts"], source, ix["indexed"],
                            ix["indexed"] + ix["included"],
                            ix["num_buckets"])
            compared.append((rec["digest"], got))
        elif rec["digest"] == want:
            continue
        else:  # the same files as a cycle compared row for row
            got = next(g for d, g in compared if d == rec["digest"])
        for k, v in got.items():
            out[k] += v
    return out


@contextlib.contextmanager
def control():
    """The control build: buckets by the first indexed column modulo the
    bucket count, in place of the engine's hash, the shortcut that would
    tempt a later change. It breaks the configuration's guarantee that a
    row sits in the bucket the engine's hash gives it."""
    import jax.numpy as jnp
    from hyperspace_tpu.ops import index_build

    def modulo(table, indexed_cols, num_buckets):
        key = table.column(indexed_cols[0]).data
        return (key % num_buckets).astype(jnp.int32)

    kept = index_build.bucket_ids_for
    index_build.bucket_ids_for = modulo
    try:
        yield
    finally:
        index_build.bucket_ids_for = kept
