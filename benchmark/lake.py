"""A TPC-H lake from a seed, in the shapes of the spec's dbgen (TPC-H
v3.0.1, section 4.2.3), written as parquet.

Every table has all of its spec columns. Decimals are float64 and dates
date32 (the engine has no decimal type); keys are int64. Row counts, key
domains and the relations between columns follow the spec:

- orders: ``SF * 1,500,000`` rows. ``o_orderkey`` is sparse, as dbgen's:
  of every 32 keys only the first 8 are used. ``o_custkey`` is uniform over
  the customers whose key is not a multiple of 3. ``o_orderdate`` is
  uniform over [1992-01-01, 1998-12-31 - 151 days].
- lineitem: each order has 1 to 7 lineitems, drawn uniformly (about
  ``SF * 6,000,000`` rows). Ship, commit and receipt dates follow the
  order date; ``l_extendedprice`` is the quantity times the part's retail
  price; return flag and line status follow the current date 1995-06-17.
- orders' ``o_totalprice`` and ``o_orderstatus`` are derived from their
  lineitems, as the spec says.
- part is not written: ``l_partkey`` is uniform over its ``SF * 200,000``
  keys, and ``l_extendedprice`` uses the spec's retail-price formula.

Comment text is cut from a pool of words from a fixed list, the way dbgen
cuts comments from its text pool. The same seed gives the same files.
Every seed gives tables of the same sizes: the seed deals a fixed multiset
of lineitem counts and order dates out to the orders, and draws every
other value itself.
"""

from __future__ import annotations

import datetime
import os
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

EPOCH = datetime.date(1970, 1, 1)
START_DATE = datetime.date(1992, 1, 1)
CURRENT_DATE = datetime.date(1995, 6, 17)
END_DATE = datetime.date(1998, 12, 31)

PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIP_INSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                 "TAKE BACK RETURN"]
SHIP_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
# The word list comments are cut from: dbgen's nouns, verbs, adjectives,
# adverbs and prepositions.
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias patterns forges braids hockey "
    "players frays warhorses dugouts notornis epitaphs pearls tithes waters "
    "orbits gifts sheaves depths sentiments decoys realms pains grouches "
    "escapades sleep wake are cajole haggle nag use boost affix detect "
    "integrate maintain nod was lose sublate solve thrash promise engage "
    "hinder print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt furious sly careful "
    "blithe quick fluffy slow quiet ruthless thin close dogged daring brave "
    "stealthy permanent enticing idle busy regular final ironic even bold "
    "silent sometimes always never furiously slyly carefully blithely "
    "quickly fluffily slowly quietly ruthlessly thinly closely doggedly "
    "daringly bravely stealthily permanently enticingly idly busily "
    "regularly finally ironically evenly boldly silently about above "
    "according to across after against along alongside of among around at "
    "atop before behind beneath beside besides between beyond by despite "
    "during except for from in place inside instead into near on outside "
    "over past since through throughout toward under until up upon without "
    "with within").split()

TEXT_POOL_BYTES = 1 << 22
SIZES_SEED = 20260101


def days(d: datetime.date) -> int:
    """Days since 1970-01-01, the date32 encoding."""
    return (d - EPOCH).days


def order_key(i: np.ndarray) -> np.ndarray:
    """The spec's sparse order key of the i-th order (0-based): the first 8
    of every 32 keys."""
    i = np.asarray(i, dtype=np.int64)
    return (i // 8) * 32 + (i % 8) + 1


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE (spec 4.2.3): (90000 + ((key/10) mod 20001)
    + 100 * (key mod 1000)) / 100."""
    k = np.asarray(partkey, dtype=np.int64)
    return (90000 + (k // 10) % 20001 + 100 * (k % 1000)) / 100.0


def _text_pool(rng: np.random.Generator) -> np.ndarray:
    """TEXT_POOL_BYTES of space-separated words, as uint8."""
    encoded = [w.encode() for w in WORDS]
    width = max(len(w) for w in encoded) + 1
    table = np.full((len(encoded), width), ord(" "), dtype=np.uint8)
    lengths = np.empty(len(encoded), dtype=np.int64)
    for i, w in enumerate(encoded):
        table[i, :len(w)] = np.frombuffer(w, dtype=np.uint8)
        lengths[i] = len(w) + 1
    n_words = TEXT_POOL_BYTES // 4
    ids = rng.integers(0, len(encoded), n_words)
    rows = table[ids]
    keep = np.arange(width)[None, :] < lengths[ids][:, None]
    return rows[keep][:TEXT_POOL_BYTES]


def _text(rng: np.random.Generator, pool: np.ndarray, n: int, lo: int,
          hi: int) -> pa.Array:
    """``n`` comments of ``lo`` to ``hi`` characters, consecutive cuts of
    the pool from a random start."""
    lengths = rng.integers(lo, hi + 1, n)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    start = int(rng.integers(0, len(pool)))
    data = np.resize(np.roll(pool, -start), int(offsets[-1]))
    return pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data))


def _choice(rng: np.random.Generator, values: List[str], n: int) -> pa.Array:
    idx = pa.array(rng.integers(0, len(values), n).astype(np.int32))
    return pa.DictionaryArray.from_arrays(idx, pa.array(values)) \
        .cast(pa.string())


def _dates(d: np.ndarray) -> pa.Array:
    return pa.array(d.astype(np.int32), type=pa.int32()).cast(pa.date32())


@dataclass
class Lake:
    """Where each table was written and how many rows it has."""

    dirs: Dict[str, str] = field(default_factory=dict)
    rows: Dict[str, int] = field(default_factory=dict)
    files: Dict[str, int] = field(default_factory=dict)


def generate(scale_factor: float, seed: int,
             tables=("lineitem", "orders")) -> Dict[str, pa.Table]:
    """lineitem, and orders where ``tables`` names it, as arrow tables in
    order-key order (as dbgen writes them). Orders are drawn after
    lineitem, so lineitem is the same with or without them."""
    rng = np.random.default_rng(seed)
    pool = _text_pool(rng)
    n_od = int(round(scale_factor * 1_500_000))
    n_pt = int(round(scale_factor * 200_000))
    n_cust = int(round(scale_factor * 150_000))
    n_supp = int(round(scale_factor * 10_000))

    # Every seed gets the same sizes: the multiset of lineitems per order
    # and of order dates is drawn once (SIZES_SEED) and the seed only
    # deals them out to the orders. Row counts, and the rows of each
    # date-partitioned file, are then equal across seeds, so every seed
    # runs the same compiled programs.
    sizes = np.random.default_rng(SIZES_SEED)
    base_per_order = sizes.integers(1, 8, n_od)
    base_odate = sizes.integers(days(START_DATE),
                                days(END_DATE) - 151 + 1, n_od)

    # orders (the lineitem-derived columns are filled in below).
    okeys = order_key(np.arange(n_od))
    j = rng.integers(0, n_cust - n_cust // 3, n_od)
    custkey = j + j // 2 + 1
    odate = base_odate[rng.permutation(n_od)]

    # lineitem: 1 to 7 per order.
    per_order = base_per_order[rng.permutation(n_od)]
    n_li = int(per_order.sum())
    owner = np.repeat(np.arange(n_od), per_order)
    first = np.zeros(n_od, dtype=np.int64)
    np.cumsum(per_order[:-1], out=first[1:])
    linenumber = np.arange(n_li) - np.repeat(first, per_order) + 1
    partkey = rng.integers(1, n_pt + 1, n_li)
    supp_i = rng.integers(0, 4, n_li)
    suppkey = (partkey + supp_i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1
    quantity = rng.integers(1, 51, n_li).astype(np.float64)
    extprice = np.round(quantity * retail_price(partkey), 2)
    discount = rng.integers(0, 11, n_li) / 100.0
    tax = rng.integers(0, 9, n_li) / 100.0
    li_odate = odate[owner]
    shipdate = li_odate + rng.integers(1, 122, n_li)
    commitdate = li_odate + rng.integers(30, 91, n_li)
    receiptdate = shipdate + rng.integers(1, 31, n_li)
    current = days(CURRENT_DATE)
    returned = rng.integers(0, 2, n_li)
    returnflag = np.where(receiptdate <= current,
                          np.where(returned == 1, 1, 0), 2)
    shipped = shipdate <= current
    linestatus = np.where(shipped, 0, 1)

    lineitem = pa.table({
        "l_orderkey": pa.array(okeys[owner]),
        "l_partkey": pa.array(partkey),
        "l_suppkey": pa.array(suppkey),
        "l_linenumber": pa.array(linenumber.astype(np.int32)),
        "l_quantity": pa.array(quantity),
        "l_extendedprice": pa.array(extprice),
        "l_discount": pa.array(discount),
        "l_tax": pa.array(tax),
        "l_returnflag": pa.DictionaryArray.from_arrays(
            pa.array(returnflag.astype(np.int32)),
            pa.array(["A", "R", "N"])).cast(pa.string()),
        "l_linestatus": pa.DictionaryArray.from_arrays(
            pa.array(linestatus.astype(np.int32)),
            pa.array(["F", "O"])).cast(pa.string()),
        "l_shipdate": _dates(shipdate),
        "l_commitdate": _dates(commitdate),
        "l_receiptdate": _dates(receiptdate),
        "l_shipinstruct": _choice(rng, SHIP_INSTRUCT, n_li),
        "l_shipmode": _choice(rng, SHIP_MODES, n_li),
        "l_comment": _text(rng, pool, n_li, 10, 43),
    })

    out = {"lineitem": lineitem}
    if "orders" not in tables:
        return out
    charge = extprice * (1 + tax) * (1 - discount)
    totalprice = np.round(np.add.reduceat(charge, first), 2)
    n_shipped = np.add.reduceat(shipped.astype(np.int64), first)
    status = np.where(n_shipped == per_order, 0,
                      np.where(n_shipped == 0, 1, 2))
    out["orders"] = pa.table({
        "o_orderkey": pa.array(okeys),
        "o_custkey": pa.array(custkey),
        "o_orderstatus": pa.DictionaryArray.from_arrays(
            pa.array(status.astype(np.int32)),
            pa.array(["F", "O", "P"])).cast(pa.string()),
        "o_totalprice": pa.array(totalprice),
        "o_orderdate": _dates(odate),
        "o_orderpriority": _choice(rng, PRIORITIES, n_od),
        "o_clerk": pa.array(np.char.add(
            "Clerk#", np.char.zfill(
                rng.integers(1, max(int(scale_factor * 1000), 1) + 1, n_od)
                .astype(str), 9))),
        "o_shippriority": pa.array(np.zeros(n_od, dtype=np.int32)),
        "o_comment": _text(rng, pool, n_od, 19, 78),
    })
    return out


def _split_rows(table: pa.Table, n_files: int):
    step = -(-table.num_rows // n_files)
    for i in range(n_files):
        piece = table.slice(i * step, step)
        if piece.num_rows:
            yield piece


def write_lake(root: str, config: dict, seed: int) -> Lake:
    """Write the tables ``config["tables"]`` names under ``root``.

    Each table's entry ``{"files": n}`` cuts it into n files of
    consecutive rows in key order, or in the order of the column the entry
    names under ``sort_by`` (a stable sort), as an ingest that sorts by
    date and caps the rows per file would write it."""
    tables = generate(config["scale_factor"], seed,
                      tuple(config["tables"]))
    lake = Lake()
    for name, layout in config["tables"].items():
        table = tables[name]
        out = os.path.join(root, name)
        os.makedirs(out)
        if "sort_by" in layout:
            key = table.column(layout["sort_by"]).to_numpy()
            table = table.take(pa.array(np.argsort(key, kind="stable")))
        n = 0
        for n, piece in enumerate(_split_rows(table, layout["files"]), 1):
            pq.write_table(piece, os.path.join(out, f"part-{n:05d}.parquet"))
        lake.dirs[name] = out
        lake.rows[name] = table.num_rows
        lake.files[name] = n
    return lake
